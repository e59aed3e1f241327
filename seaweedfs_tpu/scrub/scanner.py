"""Scrub scanner: walk stored bytes and recompute their checksums.

Two surfaces, matching the two on-disk formats:

  * normal volumes — every LIVE needle record in the .dat (the copy
    the needle map points at; dead overwrites and tombstoned garbage
    are vacuum's business, not corruption) gets its masked CRC
    recomputed via the same `verify_needle_integrity` predicate the
    SEAWEED_VERIFY_READS read gate uses.
  * EC volumes — needle-level, on the one server that verifies the
    volume (scrub/daemon.py): in a full pass the daemon walks each
    volume's .ecx once (StagedSweep) and checks every live needle in
    the bytes the pass's stripe verify has already read into its
    staging buffers — rows of this server's shards and rows pulled
    from their holders alike — on the volume's writer lane; what that
    cannot see (a volume the verify declines, a tiered shard, a needle
    spread over more than two spans) is read into a worker's reused
    buffer, from the local shard or from its holder (`fetch`), and
    checked where it lies, several needles in flight. Both check a record with ONE test (header, attributes,
    CRC chained over its pieces); a needle that does not come out
    clean is re-assembled and parsed the copied way, which alone calls
    it corrupt, and a failure is localized to the data shard at fault
    by single-shard-exclusion reconstruction;
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
from typing import Callable, Iterator, List, Optional, Sequence, Set, Tuple

import numpy as np

from seaweedfs_tpu.ec.ec_volume import EcShardNotFound, EcVolume
from seaweedfs_tpu.ec.fleet import FLEET_READERS
from seaweedfs_tpu.ec.shard_bits import DATA_SHARDS
from seaweedfs_tpu.native import rs_native
from seaweedfs_tpu.ops.rs_code import ReedSolomon
from seaweedfs_tpu.scrub.phases import phase
from seaweedfs_tpu.stats.metrics import (ScrubNeedleSourceCounter,
                                         ScrubNeedlesCounter,
                                         ScrubSweepSecondsHistogram)
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import (VERSION3, DataCorruptionError,
                                          Needle, NeedleError, actual_size,
                                          mask_crc,
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
    """One EC volume's needle sweep."""

    bytes_scanned: int = 0
    needles_verified: int = 0
    corrupt: List[int] = field(default_factory=list)   # needle ids
    bad_data_shards: Set[int] = field(default_factory=set)


# Needles in flight in the EC sweep. Its workers read shard files, so
# this is the fleet's reader-pool width: one number for "threads that
# read shard files".
SWEEP_WORKERS = FLEET_READERS

# A worker's buffer grows to the largest record it met, up to this (8x
# the filer's chunk; the workers' buffers are the sweep's whole memory,
# SWEEP_WORKERS x this at most). A larger record takes the copied path,
# one at a time as before the pool: it holds the record three times.
# The staged check carries no record over this across a span's end.
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
_SOURCE = {s: ScrubNeedleSourceCounter.labels(s)
           for s in ("staged", "carried", "read")}

# One live needle of the walk: (.ecx position, key, size, placed, length),
# placed = [(shard id, shard offset, bytes)] in record order.
_Needle = Tuple[int, int, int, List[Tuple[int, int, int]], int]


def _live_needles(ecv: EcVolume, version: int) -> Iterator[_Needle]:
    """The .ecx walk: every live needle, in .ecx order, wherever its
    intervals lie — the server that verifies a volume checks all of
    its needles, and the other holders none."""
    for i in range(len(ecv._keys)):
        try:
            _, size, intervals = ecv.locate_index(i, version)
        except NeedleError:
            continue  # tombstoned (now, or under the sweep)
        placed = [iv.to_shard_and_offset(ecv.large_block, ecv.small_block)
                  + (iv.size,) for iv in intervals]
        yield i, int(ecv._keys[i]), size, placed, \
            sum(ln for _, _, ln in placed)


def scan_ec_volume_needles(ecv: EcVolume, version: int = 3,
                           throttler=None,
                           rs: Optional[ReedSolomon] = None,
                           staged: Optional["StagedSweep"] = None,
                           fetch: Optional[Callable] = None
                           ) -> EcNeedleScan:
    """CRC-verify every live .ecx needle of the volume.

    A needle is read into a worker's buffer and checked where it lies
    (`_EcSweep`); one that is not clean there goes through the copied
    path, and a CRC failure is localized by single-shard exclusion:
    re-read the needle with each touched data shard treated as missing
    (RS reconstruction from the other shards); the exclusion that makes
    the CRC pass names the corrupt shard. An interval on a shard this
    server does not hold is read from its holder, `fetch(sid, offset,
    length) -> bytes or None` (the server's remote shard reader), and
    is reconstructed from the other shards where that gives nothing.

    `staged`: the volume's needles as the pass's stripe verify saw them
    (StagedSweep, after the verify). Those it found clean are counted
    and not read again, those it found not clean go straight to the
    copied path, and only the needles it never saw are read here.
    """
    res = EcNeedleScan()
    sweep = _EcSweep(ecv, version, rs, fetch)
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
        if staged is None:
            needles = _live_needles(ecv, version)
        else:
            res.needles_verified = staged.clean + len(staged.unclean)
            res.bytes_scanned = staged.clean_bytes
            for i, key, _, placed, length in staged.unclean:
                retire(sweep.copied(placed, length), i, key)
            needles = staged.residual()
        try:
            for i, key, size, placed, length in needles:
                res.needles_verified += 1
                _SOURCE["read"].inc()
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
                 rs: Optional[ReedSolomon], fetch: Optional[Callable]):
        self.ecv = ecv
        self.version = version
        self.rs = rs
        self.fetch = fetch
        self._mine = threading.local()   # .view: this thread's buffer
        self._oversize = threading.Lock()

    def check(self, key: int, size: int, placed,
              length: int) -> Tuple[int, Optional[Set[int]]]:
        """One needle: (bytes read, None if clean, else the data shards
        its corruption was localized to)."""
        if self._clean_in_place(key, size, placed, length):
            _NEEDLES["in_place"].inc()
            return length, None
        return self.copied(placed, length)

    def copied(self, placed,
               length: int) -> Tuple[int, Optional[Set[int]]]:
        """check_copied of one needle, counted and timed."""
        t0 = time.perf_counter()
        with self._oversize if length > _BUFFER_CAP \
                else contextlib.nullcontext():
            got, bad = check_copied(self.ecv, placed, self.version, self.rs,
                                    self.fetch)
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
        if length > _BUFFER_CAP or any(_tiered(self.ecv, sid)
                                       for sid, _, _ in placed):
            return False
        rec = self._buffer(length)
        t0 = time.perf_counter()
        at = 0
        for sid, off, ln in placed:
            shard = shards.get(sid)
            if shard is None:
                piece = _piece(self.ecv, sid, off, ln, self.fetch, self.rs)
                rec[at:at + len(piece)] = piece
                got = len(piece)
            else:
                got = shard.read_into(off, rec[at:at + ln])
            if got != ln:
                break  # a truncated shard: the copied path's evidence
            at += ln
        t1 = time.perf_counter()
        _STEP["read"].observe(t1 - t0)
        if at != length:
            return False
        clean = _record_is_clean([rec], key, size, self.version)
        _STEP["check"].observe(time.perf_counter() - t1)
        return clean


class StagedSweep:
    """One EC volume's needle sweep inside the pass's stripe verify
    (`fleet_verify_ec_files(on_span=)`), so that its data shards are
    read once. Made before the verify: the .ecx walked once, as a
    snapshot, each needle's placed intervals ordered by the lowest
    shard offset they touch. During it, on the volume's writer lane,
    every span of the verify hands `take` its data rows, and a needle
    whose intervals all lie in the span is checked in them with the
    sweep's one record test. A needle across the span's end has the
    pieces before it COPIED into a spare buffer the volume's sweep
    reuses span after span (the needles of one shard row, a few MiB;
    holding the staging buffer instead would keep a whole [14, lanes]
    stripe out of the verify's rotation for one more dispatch, and the
    readers, the pass's busiest stage, would have one buffer fewer to
    fill ahead; and where a pass has more volumes than a buffer has
    spans, a volume's next span lies several dispatches on, so it
    would pin several) and is checked when the next span of the volume
    arrives on the same lane. The staged check only ACCEPTS: after the
    verify, `scan_ec_volume_needles(staged=)` takes what it did not
    find clean through the copied path and reads from disk what it
    never saw: a needle on a tiered shard, one spread over more than
    two spans, every needle of a volume the verify declined or of a
    pass with no spans (the mesh's)."""

    def __init__(self, ecv: EcVolume, version: int = 3):
        self.version = version
        self._later: List[_Needle] = []   # for the disk sweep
        ahead = []
        for needle in _live_needles(ecv, version):
            placed = needle[3]
            if any(_tiered(ecv, sid) for sid, _, _ in placed):
                self._later.append(needle)
                continue
            ahead.append((min(off for _, off, _ in placed),
                          max(off + ln for _, off, ln in placed), needle))
        ahead.sort(key=lambda a: a[0])
        self._ahead = deque(ahead)   # (lowest offset, end, needle)
        # needles across the last span's end: the pieces before it,
        # views of _spare
        self._carry: List[Tuple[_Needle, list]] = []
        self._spare = np.empty(0, dtype=np.uint8)
        self.clean = self.clean_bytes = 0
        self.unclean: List[_Needle] = []   # for the copied path

    def take(self, offset: int, valid: int, rows) -> None:
        """One span of the verify, on the volume's writer lane, in
        offset order: shard offsets [offset, offset + valid), rows[sid]
        their bytes of data shard sid. Nothing of `rows` is kept after
        this returns but copies."""
        t0 = time.perf_counter()
        end = offset + valid
        for needle, before in self._carry:
            self._check("carried", needle,
                        _pieces(needle[3], rows, offset, before))
        crossing = []
        while self._ahead and self._ahead[0][0] < end:
            _, hi, needle = self._ahead.popleft()
            if hi <= end:
                self._check("staged", needle,
                            _pieces(needle[3], rows, offset))
            elif hi <= end + valid and needle[4] <= _BUFFER_CAP:
                # every span but a volume's last is `valid` wide, and
                # nothing crosses the last one's end
                crossing.append(needle)
            else:
                self._later.append(needle)
        self._carry = self._copy_before(crossing, rows, offset, end)
        _STEP["check"].observe(time.perf_counter() - t0)

    def _copy_before(self, crossing: List[_Needle], rows, offset: int,
                     end: int) -> list:
        """(needle, its pieces before `end` as views of _spare) for each
        needle of `crossing`. The carries before them are checked
        already, so _spare is free to take these."""
        cut = [[max(min(off + ln, end) - off, 0) for _, off, ln in needle[3]]
               for needle in crossing]
        need = sum(map(sum, cut))
        if len(self._spare) < need:
            self._spare = np.empty(max(need, 2 * len(self._spare)),
                                   dtype=np.uint8)
        carry, at = [], 0
        for needle, lens in zip(crossing, cut):
            before = []
            for (sid, off, _), n in zip(needle[3], lens):
                piece = self._spare[at:at + n]
                piece[:] = rows[sid, off - offset:off - offset + n]
                before.append(piece)
                at += n
            carry.append((needle, before))
        return carry

    def _check(self, source: str, needle: _Needle, pieces) -> None:
        _, key, size, _, length = needle
        _SOURCE[source].inc()
        if _record_is_clean(pieces, key, size, self.version):
            _NEEDLES["in_place"].inc()
            self.clean += 1
            self.clean_bytes += length
        else:
            self.unclean.append(needle)

    def residual(self) -> List[_Needle]:
        """After the verify: the needles the staged check never saw, in
        .ecx order."""
        return sorted(self._later
                      + [needle for needle, _ in self._carry]
                      + [needle for _, _, needle in self._ahead],
                      key=lambda needle: needle[0])


def _pieces(placed, rows, at: int, before: Sequence = ()) -> list:
    """A record's bytes in order as pieces: views of the span `rows`,
    which starts at shard offset `at`, after `before[j]`, what was
    copied of interval j from the span before it."""
    out = []
    for j, (sid, off, ln) in enumerate(placed):
        if before and len(before[j]):
            out.append(before[j])
        start = max(off, at)
        if start < off + ln:
            out.append(rows[sid, start - at:off + ln - at])
    return out


def _gather(pieces, start: int, stop: int) -> bytes:
    """Bytes [start, stop) of the record `pieces` make."""
    out, at = [], 0
    for p in pieces:
        n = len(p)
        if at < stop and at + n > start:
            out.append(bytes(p[max(start - at, 0):min(stop - at, n)]))
        at += n
    return b"".join(out)


def _crc(pieces, start: int, stop: int) -> int:
    """The raw CRC32C of bytes [start, stop) of the record `pieces`
    make, chained over the pieces where they lie."""
    c, at = 0, 0
    for p in pieces:
        n = len(p)
        if at < stop and at + n > start:
            c = rs_native.crc32c(p[max(start - at, 0):min(stop - at, n)], c)
        at += n
    return c


def _record_is_clean(pieces, key: int, size: int, version: int) -> bool:
    """Is the whole stored record that `pieces` make in order (buffers:
    views of a read buffer or of a staging buffer's rows, or bytes) the
    needle the .ecx entry (key, size) promises, with a payload that
    matches its stored checksum? True only if the header's id and size
    are the entry's, the attribute walk lands on the checksum and the
    CRC chained over the payload's pieces agrees: every such record
    parses under Needle.from_bytes too. False says nothing: ask the
    copied path."""
    tail = t.NEEDLE_HEADER_SIZE + size   # the checksum's place
    data_at = meta_at = tail
    head = _gather(pieces, 0, t.NEEDLE_HEADER_SIZE + (4 if size > 0 else 0))
    try:
        if size > 0:
            (data_size,) = struct.unpack_from(">I", head,
                                              t.NEEDLE_HEADER_SIZE)
            data_at = t.NEEDLE_HEADER_SIZE + 4
            meta_at = data_at + data_size   # the flags byte, before `tail`
            if meta_at >= tail:
                return False
        meta_end = tail + t.NEEDLE_CHECKSUM_SIZE + \
            (t.TIMESTAMP_SIZE if version == VERSION3 else 0)
        n = Needle.from_disk_meta(head, _gather(pieces, meta_at, meta_end),
                                  meta_at - data_at, version)
    except PARSE_ERRORS:
        return False
    if n.id != key or n.size != size:
        return False
    return size == 0 or n.checksum == mask_crc(_crc(pieces, data_at, meta_at))


def _tiered(ecv: EcVolume, sid: int) -> bool:
    """Shard `sid` is this server's and lives in a cloud backend."""
    shard = ecv.shards.get(sid)
    return shard is not None and shard.is_remote


def _piece(ecv: EcVolume, sid: int, off: int, ln: int,
           fetch: Optional[Callable], rs: Optional[ReedSolomon]) -> bytes:
    """Bytes [off, off + ln) of shard `sid`: this server's copy, else
    its holder's (`fetch`), else reconstructed from the shards that
    can be reached; b"" when none of that gives them."""
    shard = ecv.shards.get(sid)
    if shard is not None:
        return shard.read_at(off, ln)
    data = fetch(sid, off, ln) if fetch is not None else None
    if data is not None and len(data) == ln:
        return data
    try:
        return ecv._recover_interval(sid, off, ln, fetch, rs or ReedSolomon())
    except EcShardNotFound:
        return b""


def check_copied(ecv: EcVolume, placed, version: int,
                 rs: Optional[ReedSolomon],
                 fetch: Optional[Callable] = None
                 ) -> Tuple[int, Optional[Set[int]]]:
    """One needle the copied way — read_at, join, Needle.from_bytes —
    which decides corrupt: (bytes read, None if the record parses and
    its CRC holds, else the data shards `_localize_bad_shard` names).
    An interval on a shard another server holds is read through
    `fetch`."""
    blob = b"".join(_piece(ecv, sid, off, ln, fetch, rs)
                    for sid, off, ln in placed)
    try:
        Needle.from_bytes(blob, version)
    except PARSE_ERRORS:  # CRC mismatch or a torn/short parse
        return len(blob), _localize_bad_shard(ecv, placed, version, rs,
                                              fetch)
    return len(blob), None


def _localize_bad_shard(ecv: EcVolume, placed, version: int,
                        rs: Optional[ReedSolomon],
                        fetch: Optional[Callable] = None) -> Set[int]:
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
                                                        fetch, rs))
                else:
                    pieces.append(_piece(ecv, sid, off, ln, fetch, rs))
            Needle.from_bytes(b"".join(pieces), version)
        except PARSE_ERRORS:
            continue
        return {suspect}
    return set()
