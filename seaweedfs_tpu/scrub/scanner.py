"""Scrub scanner: walk stored bytes and recompute their checksums.

Two surfaces, matching the two on-disk formats:

  * normal volumes — every LIVE needle record in the .dat (the copy
    the needle map points at; dead overwrites and tombstoned garbage
    are vacuum's business, not corruption) gets its masked CRC
    recomputed via the same `verify_needle_integrity` predicate the
    SEAWEED_VERIFY_READS read gate uses.
  * EC volumes — needle-level: each live .ecx entry is re-assembled
    from LOCAL shards and CRC-checked, and a failure is localized to
    the data shard at fault by single-shard-exclusion reconstruction;
    stripe-level: `ec/fleet.fleet_verify_ec_files` re-encodes the data
    shards through the fused dispatcher and compares parity (that call
    is batched across many volumes by the daemon, not per-volume here).

The scanner only ever reads; every repair decision belongs to
scrub/planner.py.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import List, Optional, Set, Tuple

from seaweedfs_tpu.ec.ec_volume import EcVolume
from seaweedfs_tpu.ec.shard_bits import DATA_SHARDS
from seaweedfs_tpu.ops.rs_code import ReedSolomon
from seaweedfs_tpu.scrub.phases import phase
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import (DataCorruptionError, Needle,
                                          NeedleError, actual_size,
                                          verify_needle_integrity)
from seaweedfs_tpu.storage.volume import Volume

# What a corrupt record can throw at parse time: a CRC failure is a
# clean DataCorruptionError, but a TRUNCATED/garbled record dies lower
# — struct.unpack on a short tail, body[off] past the end. All of it
# is corruption evidence; none of it may abort the scrub pass.
PARSE_ERRORS = (NeedleError, struct.error, IndexError, ValueError)


@dataclass
class NeedleScan:
    """One volume's needle sweep."""

    bytes_scanned: int = 0
    needles_verified: int = 0
    corrupt: List[Tuple[int, Needle]] = field(default_factory=list)
    # (dat offset, parsed-but-CRC-bad needle) — header metadata
    # (id/cookie/checksum) is still the repair planner's handle on it


def scan_volume(v: Volume, throttler=None) -> NeedleScan:
    """Verify every live needle of one mounted volume.

    Uses the volume's own scan fd (scan_needles), so a long scrub
    never races the serving read/write handles; the needle map is
    consulted per record to skip dead copies.
    """
    res = NeedleScan()
    with phase("scan", vid=v.id):
        for offset, n in v.scan_needles():
            nv = v.nm.get(n.id)
            if nv is None or nv.offset != offset or \
                    not t.size_is_valid(nv.size):
                continue  # overwritten or deleted: not the live copy
            length = actual_size(n.size, v.version)
            res.bytes_scanned += length
            res.needles_verified += 1
            if throttler is not None:
                throttler.maybe_slowdown(length)
            try:
                verify_needle_integrity(n)
            except DataCorruptionError:
                res.corrupt.append((offset, n))
    return res


@dataclass
class EcNeedleScan:
    """One EC volume's needle sweep over local shards."""

    bytes_scanned: int = 0
    needles_verified: int = 0
    corrupt: List[int] = field(default_factory=list)   # needle ids
    bad_data_shards: Set[int] = field(default_factory=set)
    skipped_remote: int = 0   # needles touching non-local shards


def scan_ec_volume_needles(ecv: EcVolume, version: int = 3,
                           throttler=None,
                           rs: Optional[ReedSolomon] = None) -> EcNeedleScan:
    """CRC-verify every live .ecx needle assembled from LOCAL shards.

    A CRC failure is localized by single-shard exclusion: re-read the
    needle with each touched data shard treated as missing (RS
    reconstruction from the other shards); the exclusion that makes
    the CRC pass names the corrupt shard. Needles spanning shards this
    server doesn't hold are skipped (their holder scrubs them).
    """
    res = EcNeedleScan()
    with phase("scan_ec", vid=ecv.volume_id):
        for i in range(len(ecv._keys)):
            size = int(ecv._sizes[i])
            if t.size_is_deleted(size) or size < 0:
                continue
            key = int(ecv._keys[i])
            try:
                _, _, intervals = ecv.locate_needle(key, version)
            except NeedleError:
                continue  # tombstoned between snapshot and read
            placed = [iv.to_shard_and_offset(ecv.large_block,
                                             ecv.small_block) + (iv.size,)
                      for iv in intervals]
            if any(sid not in ecv.shards for sid, _, _ in placed):
                res.skipped_remote += 1
                continue
            blob = b"".join(ecv.shards[sid].read_at(off, ln)
                            for sid, off, ln in placed)
            res.bytes_scanned += len(blob)
            res.needles_verified += 1
            if throttler is not None:
                throttler.maybe_slowdown(len(blob))
            try:
                Needle.from_bytes(blob, version)
            except PARSE_ERRORS:  # CRC mismatch or a torn/short parse
                res.corrupt.append(key)
                res.bad_data_shards |= _localize_bad_shard(
                    ecv, placed, version, rs)
    return res


def _localize_bad_shard(ecv: EcVolume, placed, version: int,
                        rs: Optional[ReedSolomon]) -> Set[int]:
    """Which single data shard, if excluded and RS-reconstructed,
    makes the needle's CRC pass? Empty set = not localizable this way
    (multi-shard damage, or parity too corrupt to reconstruct with) —
    the planner then falls back on the stripe-verify evidence."""
    rs = rs or ReedSolomon()
    candidates = sorted({sid for sid, _, _ in placed if sid < DATA_SHARDS})
    for suspect in candidates:
        try:
            pieces = []
            for sid, off, ln in placed:
                if sid == suspect:
                    pieces.append(ecv._recover_interval(sid, off, ln,
                                                        None, rs))
                else:
                    pieces.append(ecv.shards[sid].read_at(off, ln))
            Needle.from_bytes(b"".join(pieces), version)
        except PARSE_ERRORS:
            continue
        return {suspect}
    return set()
