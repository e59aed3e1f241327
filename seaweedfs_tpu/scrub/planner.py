"""Scrub repair planner: classify damage, quarantine, reconstruct.

Classification uses both evidence streams the scanner produces:

  * needle-CRC localization names corrupt DATA shards directly.
  * the stripe verify (re-encode vs stored parity) names corrupt
    PARITY shards — but only when the data shards are clean: a corrupt
    data shard contaminates ALL four recomputed parity streams, so
    parity mismatches are trusted only on a volume with no data-shard
    evidence.
  * when all four parity streams disagree and the needle sweep found
    nothing (damage in dead bytes of a data shard — padding, an
    overwritten record — that no live CRC covers), the syndrome probe
    localize_from_parity_deltas names the culprit: a single-byte error
    e in data shard d shifts recomputed parity row p by exactly
    M[p,d]*e in GF(2^8), so the shard whose matrix column divides all
    four observed deltas to the SAME e is the corrupt one. The Cauchy
    rows make that division ambiguous only for genuine multi-shard
    damage, which falls through to the parity verdict and is caught by
    the post-repair verify round.

Repair is quarantine-then-rebuild: each condemned .ecNN is renamed to
.ecNN.corrupt (never deleted — the operator's forensic copy), then the
fleet rebuild path reconstructs it from the surviving >=10 shards,
byte-identical to the original. It runs on the server that holds the
condemned shard: survivors it lacks are pulled from their holders
(`remote`), so a shard never moves. The volume's verifier asks a holder
for it over gRPC (VolumeEcShardsRebuild with condemned_shard_ids). RS(10,4) caps repairable damage at 4
shards per volume; anything past that is unrecoverable and stays
quarantine-free so whatever still reads, still reads.

Needle repair in normal volumes has no parity to lean on: the good
bytes come from a replica (replica_fetch), validated against the
corrupt record's own stored CRC before being rewritten in place.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

from seaweedfs_tpu.ec import fleet
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.ec.shard_bits import TOTAL_SHARDS
from seaweedfs_tpu.ops.rs_code import DATA_SHARDS, PARITY_SHARDS
from seaweedfs_tpu.scrub.phases import phase
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.storage.needle import Needle, NeedleError, masked_crc
from seaweedfs_tpu.storage.volume import Volume, VolumeError


@dataclass
class EcDamage:
    """Everything the scanner learned about one EC volume."""

    base: str
    bad_data: Set[int] = field(default_factory=set)
    parity_mismatch: Dict[int, int] = field(default_factory=dict)
    first_mismatch: Dict[int, int] = field(default_factory=dict)
    parity_checked: List[int] = field(default_factory=list)
    missing: List[int] = field(default_factory=list)


def _shard_byte(base: str, sid: int, offset: int,
                fetch: Optional[Callable] = None) -> int:
    """One byte of shard `sid`: from its file here, else from a holder
    (`fetch(sid, offset, length) -> bytes or None`)."""
    path = shard_file_name(base, sid)
    if fetch is not None and not os.path.exists(path):
        b = fetch(sid, offset, 1)
    else:
        with open(path, "rb") as f:
            f.seek(offset)
            b = f.read(1)
    return b[0] if b else 0


def localize_from_parity_deltas(base: str, offsets,
                                parity_ids=None,
                                fetch: Optional[Callable] = None) -> Set[int]:
    """Syndrome probe: name the single corrupt DATA shard behind an
    every-parity-stream mismatch (see module docstring). Probes one
    byte column per offset over the parity shards actually present
    (`parity_ids`, default all four); returns the data shards
    unambiguously identified (empty = not single-shard damage — a
    single parity row can never discriminate, so it returns nothing).
    A shard with no file here is read through `fetch`."""
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_code import coding_matrix
    m = coding_matrix()
    parity_ids = sorted(parity_ids) if parity_ids else \
        list(range(DATA_SHARDS, TOTAL_SHARDS))
    culprits: Set[int] = set()
    for offset in offsets:
        col = [_shard_byte(base, d, offset, fetch)
               for d in range(DATA_SHARDS)]
        delta = {}
        for sid in parity_ids:
            acc = 0
            for d in range(DATA_SHARDS):
                acc ^= int(gf256.GF_MUL_TABLE[m[sid, d], col[d]])
            delta[sid] = acc ^ _shard_byte(base, sid, offset, fetch)
        if not all(delta.values()):
            continue  # some parity agrees here: not a data-shard error
        cands = [d for d in range(DATA_SHARDS)
                 if len({gf256.gf_div(delta[sid], int(m[sid, d]))
                         for sid in parity_ids}) == 1]
        if len(parity_ids) >= 2 and len(cands) == 1:
            culprits.add(cands[0])
    return culprits


def classify_ec_damage(damage: EcDamage) -> Tuple[str, List[int]]:
    """-> (verdict, shard ids to rebuild). Verdicts:

    clean          nothing to do
    data           condemned data shard(s) (+ any missing files)
    parity         condemned parity shard(s) (+ any missing files)
    unrecoverable  more than PARITY_SHARDS shards condemned, or fewer
                   than DATA_SHARDS survivors to rebuild from
    """
    bad: Set[int] = set(damage.missing)
    verdict = "clean"
    if damage.bad_data:
        # data evidence wins; parity mismatches are contaminated and
        # get re-judged by the post-repair verify round
        bad |= damage.bad_data
        verdict = "data"
    elif damage.parity_mismatch:
        bad |= set(damage.parity_mismatch)
        verdict = "parity"
    elif bad:
        verdict = "data" if any(s < DATA_SHARDS for s in bad) else "parity"
    if not bad:
        return "clean", []
    if len(bad) > PARITY_SHARDS or TOTAL_SHARDS - len(bad) < DATA_SHARDS:
        return "unrecoverable", sorted(bad)
    return verdict, sorted(bad)


def quarantine_shard(base: str, shard_id: int) -> bool:
    """<base>.ecNN -> <base>.ecNN.corrupt (never deleted). A prior
    quarantine of the same shard is rotated away rather than clobbered."""
    path = shard_file_name(base, shard_id)
    if not os.path.exists(path):
        return False
    marker = path + ".corrupt"
    if os.path.exists(marker):
        os.replace(marker, marker + ".old")
    os.replace(path, marker)
    return True


def repair_ec_volume(base: str, bad_shards: List[int],
                     backend: str = "auto",
                     unmount: Optional[Callable[[int], None]] = None,
                     remount: Optional[Callable[[int], None]] = None,
                     remote: Optional["fleet.RemoteRows"] = None,
                     ) -> List[int]:
    """Quarantine + rebuild the condemned shards of one volume.

    unmount/remount hooks let the store drop its open fd on a shard
    before the rename and re-open it after the rebuild (a mounted
    EcVolumeShard holds the old inode otherwise). `remote`: the rows of
    the shards other servers hold, for survivors this server lacks.
    Returns the rebuilt shard ids; raises if fewer than DATA_SHARDS
    survivors remain.
    """
    with phase("repair", base=os.path.basename(base),
               shards=len(bad_shards)):
        for sid in bad_shards:
            if unmount is not None:
                unmount(sid)
            quarantine_shard(base, sid)
        rebuilt = fleet.fleet_rebuild_ec_files(
            [base], backend=backend, wanted=list(bad_shards),
            remote=None if remote is None else {base: remote})[base]
        for sid in bad_shards:
            if remount is not None:
                remount(sid)
        return rebuilt


def verify_ec_repair(base: str, backend: str = "auto",
                     remote: Optional["fleet.RemoteRows"] = None
                     ) -> "fleet.VerifyResult":
    """Post-repair stripe verify of ONE volume (the daemon's second
    evidence round: after a data-shard rebuild, any parity mismatch
    that remains is genuine parity damage), its remote rows from their
    holders."""
    with phase("reverify", base=os.path.basename(base)):
        return fleet.fleet_verify_ec_files(
            [base], backend=backend,
            remote=None if remote is None else {base: remote})[base]


def repair_needle(v: Volume, corrupt: Needle,
                  replica_fetch: Callable[[int, Needle], Optional[bytes]],
                  ) -> bool:
    """Rewrite one CRC-bad needle from a replica's copy.

    The corrupt record's header (id/cookie/flags/checksum) survives —
    only `data` failed its CRC — so the replica's bytes are validated
    against the LOCAL record's stored checksum before anything is
    written: a replica that is itself corrupt (or serves a newer
    overwrite) never lands here. The rewrite is a cookie-checked
    append committed directly under the volume lock with the seal
    lifted only inside that critical section — no client write can
    slip onto a sealed volume through the repair window, and routing
    through the group-commit worker (which would need the same lock)
    is bypassed. The bad record becomes dead space for vacuum.
    """
    from seaweedfs_tpu.storage.volume import _WriteRequest
    data = replica_fetch(v.id, corrupt)
    if data is None or masked_crc(data) != corrupt.checksum:
        return False
    fixed = Needle(id=corrupt.id, cookie=corrupt.cookie, data=data,
                   flags=corrupt.flags, name=corrupt.name,
                   mime=corrupt.mime, pairs=corrupt.pairs,
                   last_modified=corrupt.last_modified, ttl=corrupt.ttl)
    with trace.span("scrub.repair", vid=v.id, needle=corrupt.id):
        req = _WriteRequest("write", fixed)
        with v._lock:
            was_ro, v.read_only = v.read_only, False
            try:
                v._apply_batch([req])
            finally:
                v.read_only = was_ro
        try:
            req.wait()
        except (NeedleError, VolumeError):
            return False
    return True
