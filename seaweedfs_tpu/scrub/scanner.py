"""Scrub scanner: walk stored bytes and recompute their checksums.

Two surfaces, matching the two on-disk formats:

  * normal volumes — every LIVE needle record in the .dat (the copy
    the needle map points at; dead overwrites and tombstoned garbage
    are vacuum's business, not corruption) gets its masked CRC
    recomputed via the same `verify_needle_integrity` predicate the
    SEAWEED_VERIFY_READS read gate uses.
  * EC volumes — needle-level: each live .ecx entry is read from
    LOCAL shards into a worker's reused buffer and checked where it
    lies (header, attributes, CRC over a view), several needles in
    flight; a needle that does not come out clean there is re-assembled
    and parsed the copied way, which alone calls it corrupt, and a
    failure is localized to the data shard at fault by
    single-shard-exclusion reconstruction;
    stripe-level: `ec/fleet.fleet_verify_ec_files` re-encodes the data
    shards through the fused dispatcher and compares parity (that call
    is batched across many volumes by the daemon, not per-volume here).

The scanner only ever reads; every repair decision belongs to
scrub/planner.py.
"""

from __future__ import annotations

import contextlib
import contextvars
import struct
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import List, Optional, Set, Tuple

from seaweedfs_tpu.ec.ec_volume import EcVolume
from seaweedfs_tpu.ec.fleet import FLEET_READERS
from seaweedfs_tpu.ec.shard_bits import DATA_SHARDS
from seaweedfs_tpu.ops.rs_code import ReedSolomon
from seaweedfs_tpu.scrub.phases import phase
from seaweedfs_tpu.stats.metrics import (ScrubNeedlesCounter,
                                         ScrubSweepSecondsHistogram)
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import (VERSION3, DataCorruptionError,
                                          Needle, NeedleError, actual_size,
                                          masked_crc,
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


# Needles in flight in the EC sweep. Its workers read shard files, so
# this is the fleet's reader-pool width: one number for "threads that
# read shard files".
SWEEP_WORKERS = FLEET_READERS

# A worker's buffer grows to the largest record it met, up to this (8x
# the filer's chunk; the workers' buffers are the sweep's whole memory,
# SWEEP_WORKERS x this at most). A larger record takes the copied path,
# one at a time as before the pool: it holds the record three times.
_BUFFER_CAP = 32 << 20

# A record of at least this many bytes is handed to a worker; a smaller
# one is checked by the sweeping thread itself, in a buffer of its own:
# its cost is the interpreter's, which workers would only contend for,
# and the hand-over would be most of it.
_HANDOVER_BYTES = 256 << 10

# children resolved once at import: labels() takes a lock per call
_NEEDLES = {c: ScrubNeedlesCounter.labels(c) for c in ("in_place", "copied")}
_STEP = {s: ScrubSweepSecondsHistogram.labels(s)
         for s in ("read", "check", "copied")}


def scan_ec_volume_needles(ecv: EcVolume, version: int = 3,
                           throttler=None,
                           rs: Optional[ReedSolomon] = None) -> EcNeedleScan:
    """CRC-verify every live .ecx needle assembled from LOCAL shards.

    A needle is read into a worker's buffer and checked where it lies
    (`_EcSweep`); one that is not clean there goes through the copied
    path, and a CRC failure is localized by single-shard exclusion:
    re-read the needle with each touched data shard treated as missing
    (RS reconstruction from the other shards); the exclusion that makes
    the CRC pass names the corrupt shard. Needles spanning shards this
    server doesn't hold are skipped (their holder scrubs them).
    """
    res = EcNeedleScan()
    sweep = _EcSweep(ecv, version, rs)
    found: List[Tuple[int, int, Set[int]]] = []  # (.ecx position, key, bad)
    inflight: deque = deque()
    pool = ThreadPoolExecutor(max_workers=SWEEP_WORKERS,
                              thread_name_prefix="scrub-sweep")

    def retire(checked: Tuple[int, Optional[Set[int]]], i: int,
               key: int) -> None:
        scanned, bad = checked
        res.bytes_scanned += scanned
        if bad is not None:
            found.append((i, key, bad))

    with phase("scan_ec", vid=ecv.volume_id):
        try:
            for i in range(len(ecv._keys)):
                try:
                    _, size, intervals = ecv.locate_index(i, version)
                except NeedleError:
                    continue  # tombstoned (now, or under the sweep)
                placed = [iv.to_shard_and_offset(ecv.large_block,
                                                 ecv.small_block) + (iv.size,)
                          for iv in intervals]
                if any(sid not in ecv.shards for sid, _, _ in placed):
                    res.skipped_remote += 1
                    continue
                key = int(ecv._keys[i])
                length = sum(ln for _, _, ln in placed)
                res.needles_verified += 1
                if throttler is not None:
                    # before the hand-over: a throttled pass overshoots
                    # by the needles in flight at most
                    throttler.maybe_slowdown(length)
                if length < _HANDOVER_BYTES:
                    retire(sweep.check(key, size, placed, length), i, key)
                    continue
                if len(inflight) >= 2 * SWEEP_WORKERS:
                    fut, at, its = inflight.popleft()
                    retire(fut.result(), at, its)
                # a worker runs in a copy of the pass's context: the
                # daemon's QoS tenant follows a tiered shard's ranged GET
                inflight.append((pool.submit(
                    contextvars.copy_context().run, sweep.check, key, size,
                    placed, length), i, key))
            for fut, at, its in inflight:
                retire(fut.result(), at, its)
        finally:
            # the repair phase unmounts shards: no read outlives the sweep
            pool.shutdown(wait=True)
    found.sort()
    for _, key, bad in found:
        res.corrupt.append(key)
        res.bad_data_shards |= bad
    return res


def _new_buffer(size: int) -> bytearray:
    return bytearray(size)


class _EcSweep:
    """One EC volume's needle checks as the sweep's threads run them:
    each keeps ONE buffer for the volume, reads a record's intervals
    straight into it and checks the record there. In place
    only ACCEPTS: whatever does not come out clean is handed, unchanged,
    to `check_copied`, which alone decides corrupt and names shards."""

    def __init__(self, ecv: EcVolume, version: int,
                 rs: Optional[ReedSolomon]):
        self.ecv = ecv
        self.version = version
        self.rs = rs
        self._mine = threading.local()   # .view: this thread's buffer
        self._oversize = threading.Lock()

    def check(self, key: int, size: int, placed,
              length: int) -> Tuple[int, Optional[Set[int]]]:
        """One needle: (bytes read, None if clean, else the data shards
        its corruption was localized to)."""
        if self._clean_in_place(key, size, placed, length):
            _NEEDLES["in_place"].inc()
            return length, None
        t0 = time.perf_counter()
        with self._oversize if length > _BUFFER_CAP \
                else contextlib.nullcontext():
            got, bad = check_copied(self.ecv, placed, self.version, self.rs)
        _STEP["copied"].observe(time.perf_counter() - t0)
        _NEEDLES["copied"].inc()
        return got, bad

    def _buffer(self, length: int) -> memoryview:
        view = getattr(self._mine, "view", None)
        if view is None or len(view) < length:
            # doubled, so a volume of growing records allocates a few
            # times and not once a record; the sweeping thread's own
            # (records under _HANDOVER_BYTES) is made once
            view = self._mine.view = memoryview(_new_buffer(max(
                length, min(_BUFFER_CAP, 2 * len(view)) if view
                else _HANDOVER_BYTES)))
        return view[:length]

    def _clean_in_place(self, key: int, size: int, placed,
                        length: int) -> bool:
        shards = self.ecv.shards
        if length > _BUFFER_CAP or \
                any(shards[sid].is_remote for sid, _, _ in placed):
            return False
        rec = self._buffer(length)
        t0 = time.perf_counter()
        at = 0
        for sid, off, ln in placed:
            if shards[sid].read_into(off, rec[at:at + ln]) != ln:
                break  # a truncated shard: the copied path's evidence
            at += ln
        t1 = time.perf_counter()
        _STEP["read"].observe(t1 - t0)
        if at != length:
            return False
        clean = _record_is_clean(rec, key, size, self.version)
        _STEP["check"].observe(time.perf_counter() - t1)
        return clean


def _record_is_clean(rec: memoryview, key: int, size: int,
                    version: int) -> bool:
    """Is `rec`, a whole stored record in a buffer, the needle the .ecx
    entry (key, size) promises, with a payload that matches its stored
    checksum? True only if the header's id and size are the entry's,
    the attribute walk lands on the checksum and the CRC over the
    payload's VIEW agrees: every such record parses under
    Needle.from_bytes too. False says nothing: ask the copied path."""
    tail = t.NEEDLE_HEADER_SIZE + size   # the checksum's place
    data_at = meta_at = tail
    if size > 0:
        (data_size,) = struct.unpack_from(">I", rec, t.NEEDLE_HEADER_SIZE)
        data_at = t.NEEDLE_HEADER_SIZE + 4
        meta_at = data_at + data_size   # the flags byte, before `tail`
        if meta_at >= tail:
            return False
    meta_end = tail + t.NEEDLE_CHECKSUM_SIZE + \
        (t.TIMESTAMP_SIZE if version == VERSION3 else 0)
    try:
        n = Needle.from_disk_meta(rec, bytes(rec[meta_at:meta_end]),
                                  meta_at - data_at, version)
    except PARSE_ERRORS:
        return False
    if n.id != key or n.size != size:
        return False
    return size == 0 or n.checksum == masked_crc(rec[data_at:meta_at])


def check_copied(ecv: EcVolume, placed, version: int,
                 rs: Optional[ReedSolomon]) -> Tuple[int, Optional[Set[int]]]:
    """One needle the copied way — read_at, join, Needle.from_bytes —
    which decides corrupt: (bytes read, None if the record parses and
    its CRC holds, else the data shards `_localize_bad_shard` names)."""
    blob = b"".join(ecv.shards[sid].read_at(off, ln)
                    for sid, off, ln in placed)
    try:
        Needle.from_bytes(blob, version)
    except PARSE_ERRORS:  # CRC mismatch or a torn/short parse
        return len(blob), _localize_bad_shard(ecv, placed, version, rs)
    return len(blob), None


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
