"""EcVolume: runtime state of one erasure-coded volume on a server.

Holds mounted shard files, the key-sorted .ecx index, and the .ecj
delete journal. Needle reads resolve via binary search + interval math;
missing-shard intervals are recovered by callers through the RS decoder
(see read_needle / seaweedfs_tpu/volume_server integration).

Reference: weed/storage/erasure_coding/ec_volume.go, ec_shard.go,
ec_volume_delete.go.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from seaweedfs_tpu.ec import locate as ec_locate
from seaweedfs_tpu.ec.encoder import (
    shard_file_name, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE,
)
from seaweedfs_tpu.ec.shard_bits import ShardBits, DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.ops.rs_code import ReedSolomon
from seaweedfs_tpu.stats.metrics import (
    ReadsDecodedBytesCounter, ReadsDegradedCounter, ReadsShortShardCounter)
from seaweedfs_tpu.storage import idx as idx_codec
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import Needle, NeedleError, actual_size
from seaweedfs_tpu.util import wlog

log = wlog.logger("ec")


class EcShardNotFound(NeedleError):
    pass


# Shared fetch pool for the in-place (non-fleet) recovery fallback:
# created lazily on the FIRST degraded read, so a healthy server never
# spawns these threads (the degraded-decode-disabled perf gate).
_recover_pool: Optional[ThreadPoolExecutor] = None
_recover_pool_lock = threading.Lock()


def _get_recover_pool() -> ThreadPoolExecutor:
    global _recover_pool
    if _recover_pool is None:
        with _recover_pool_lock:
            if _recover_pool is None:
                # lint: thread-ok(shared recover pool takes explicit work items; the read seam enforces deadlines)
                _recover_pool = ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="ec-recover")
    return _recover_pool


class EcVolumeShard:
    """One mounted .ecNN shard (reference ec_shard.go:16-95).

    A shard is either LOCAL (an open file) or REMOTE (the bytes live
    in a cloud backend, recorded by the <base>.ectier sidecar —
    storage/volume_tier.move_ec_shards_to_remote): reads route through
    ranged backend GETs, the shard stays mounted, and the heartbeat
    keeps advertising it, so the COLD tier is transparent to every
    consumer of read_at (needle reads, scrub verify, remote shard
    serving, RS reconstruction rows)."""

    def __init__(self, directory: str, collection: str, vid: int,
                 shard_id: int, remote=None):
        self.collection = collection
        self.volume_id = vid
        self.shard_id = shard_id
        name = f"{collection}_{vid}" if collection else str(vid)
        self.path = shard_file_name(os.path.join(directory, name), shard_id)
        self._lock = threading.Lock()
        # read_at's lock-free fast path reads this once and falls back
        # to the local file under the lock when a concurrent download
        # leg swapped the shard mid-read (PR 9 review contract)
        self._remote = None  # guarded_by(self._lock, writes)   (BackendStorage, key) when tiered
        if remote is not None:
            storage, key, size = remote
            self._remote = (storage, key)
            self._f = None
            self.size = size
        else:
            self._f = open(self.path, "rb")
            self.size = os.path.getsize(self.path)

    @property
    def is_remote(self) -> bool:
        return self._remote is not None

    def read_at(self, offset: int, length: int) -> bytes:
        remote = self._remote
        if remote is not None:
            storage, key = remote
            try:
                return storage.read_range(key, offset, length)
            except Exception:
                # the download leg may have swapped this shard local
                # (and deleted the remote object) between our snapshot
                # and the ranged GET: serve from the file if so, else
                # surface the backend error
                with self._lock:
                    if self._f is None:
                        raise
                    self._f.seek(offset)
                    return self._f.read(length)
        with self._lock:
            if self._f is None:      # swapped remote mid-read
                storage, key = self._remote
                return storage.read_range(key, offset, length)
            self._f.seek(offset)
            return self._f.read(length)

    def read_into(self, offset: int, view: memoryview) -> int:
        """read_at's bytes placed in `view` (writable, as long as the
        read) instead of a fresh `bytes`: one positional read into the
        caller's memory, under read_at's lock, so a close or a swap
        waits for it. Returns the count, short only at the file's
        end. A tiered shard, or one swapped or unmounted mid-read,
        goes through read_at and pays its copy."""
        if self._remote is None:
            with self._lock:
                if self._f is not None:
                    fd, got = self._f.fileno(), 0
                    while got < len(view):
                        n = os.preadv(fd, [view[got:]], offset + got)
                        if n == 0:
                            break
                        got += n
                    return got
        data = self.read_at(offset, len(view))
        view[:len(data)] = data
        return len(data)

    def swap_to_remote(self, storage, key: str, size: int) -> None:
        """Serve from the backend from now on (the tier-upload handle
        swap; the caller deletes the local file afterwards)."""
        with self._lock:
            old, self._f = self._f, None
            self._remote = (storage, key)
            self.size = size
        if old is not None:
            old.close()

    def swap_to_local(self) -> None:
        """Back to the local file (tier download re-materialized it)."""
        f = open(self.path, "rb")
        size = os.path.getsize(self.path)
        with self._lock:
            self._f = f
            self._remote = None
            self.size = size

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None

    def destroy(self) -> None:
        self.close()
        if os.path.exists(self.path):
            os.remove(self.path)


class EcVolume:
    def __init__(self, directory: str, collection: str, vid: int,
                 large_block: int = LARGE_BLOCK_SIZE,
                 small_block: int = SMALL_BLOCK_SIZE):
        self.directory = directory
        self.collection = collection
        self.volume_id = vid
        self.large_block = large_block
        self.small_block = small_block
        name = f"{collection}_{vid}" if collection else str(vid)
        self.base_name = os.path.join(directory, name)
        if not os.path.exists(self.base_name + ".ecx"):
            raise FileNotFoundError(self.base_name + ".ecx")
        self._ecx = open(self.base_name + ".ecx", "r+b")
        self._ecj = open(self.base_name + ".ecj", "a+b")
        self._lock = threading.RLock()
        self.shards: Dict[int, EcVolumeShard] = {}
        # shards whose short local read was already logged (once per
        # shard, so recovery traffic is distinguishable from decay
        # without flooding the log on a hot truncated shard)
        self._short_logged: set = set()
        # remote shard location cache: shard id -> list of server urls
        self.shard_locations: Dict[int, List[str]] = {}
        self.shard_locations_refreshed_at = 0.0
        self._load_ecx()
        self.created_at = time.time()

    # -- index ---------------------------------------------------------------

    def _load_ecx(self) -> None:
        self._ecx.seek(0)
        arr = idx_codec.parse_index_bytes(self._ecx.read())
        self._keys = arr["key"].copy()
        self._offsets = arr["offset"].copy()
        # find_needle/file_count read lock-free (single-element numpy
        # stores are atomic under the GIL; a read racing a tombstone
        # sees either value, both valid); mutation takes the lock
        # lint: guard-ok(_load_ecx runs from __init__ only, before the volume is published)
        self._sizes = arr["size"].copy()  # guarded_by(self._lock, writes)

    def find_needle(self, needle_id: int) -> Tuple[int, int]:
        """Return (dat_offset, size); raises NeedleError if absent/deleted."""
        i = int(np.searchsorted(self._keys, np.uint64(needle_id)))
        if i >= len(self._keys) or self._keys[i] != needle_id:
            raise NeedleError(f"needle {needle_id:x} not in ecx")
        size = int(self._sizes[i])
        if t.size_is_deleted(size):
            raise NeedleError(f"needle {needle_id:x} deleted")
        return int(self._offsets[i]), size

    def delete_needle(self, needle_id: int) -> None:
        """Tombstone in the sorted .ecx in place + journal to .ecj
        (reference ec_volume_delete.go:13-49)."""
        with self._lock:
            i = int(np.searchsorted(self._keys, np.uint64(needle_id)))
            if i >= len(self._keys) or self._keys[i] != needle_id:
                return
            self._sizes[i] = t.TOMBSTONE_SIZE
            entry_off = i * t.NEEDLE_MAP_ENTRY_SIZE
            self._ecx.seek(entry_off + t.NEEDLE_ID_SIZE + t.OFFSET_SIZE)
            self._ecx.write((t.TOMBSTONE_SIZE & 0xFFFFFFFF).to_bytes(4, "big"))
            self._ecx.flush()
            self._ecj.seek(0, os.SEEK_END)
            self._ecj.write(needle_id.to_bytes(8, "big"))
            self._ecj.flush()

    # -- shards --------------------------------------------------------------

    def mount_shard(self, shard_id: int) -> EcVolumeShard:
        with self._lock:
            if shard_id in self.shards:
                return self.shards[shard_id]
            s = EcVolumeShard(self.directory, self.collection, self.volume_id,
                              shard_id, remote=self._remote_info(shard_id))
            self.shards[shard_id] = s
            return s

    def _remote_info(self, shard_id: int):
        """(storage, key, size) for a shard this server tiered to a
        cloud backend (<base>.ectier sidecar), else None — so a
        restart remounts COLD shards without their local files."""
        if os.path.exists(shard_file_name(self.base_name, shard_id)):
            return None             # local file wins
        from seaweedfs_tpu.storage import backend as bk
        info = bk.read_ec_tier_info(self.base_name)
        if info is None:
            return None
        rec = info["shards"].get(shard_id)
        if rec is None:
            return None
        return bk.get_backend(info["backend"]), rec["key"], rec["size"]

    def unmount_shard(self, shard_id: int) -> bool:
        with self._lock:
            s = self.shards.pop(shard_id, None)
            if s is None:
                return False
            s.close()
            return True

    @property
    def shard_bits(self) -> ShardBits:
        return ShardBits.of(*self.shards.keys())

    @property
    def shard_size(self) -> int:
        for s in self.shards.values():
            return s.size
        # no local shards: derive from any shard file present
        for i in range(TOTAL_SHARDS):
            p = shard_file_name(self.base_name, i)
            if os.path.exists(p):
                return os.path.getsize(p)
        return 0

    # -- needle read ---------------------------------------------------------

    def locate_needle(self, needle_id: int, version: int = 3):
        """(offset, size, intervals) for the WHOLE needle record."""
        offset, size = self.find_needle(needle_id)
        return offset, size, self._record_intervals(offset, size, version)

    def locate_index(self, i: int, version: int = 3):
        """locate_needle for the i-th .ecx entry: a sweep of the whole
        index holds the position already and searches for nothing."""
        size = int(self._sizes[i])
        if t.size_is_deleted(size):
            raise NeedleError(f"needle {int(self._keys[i]):x} deleted")
        offset = int(self._offsets[i])
        return offset, size, self._record_intervals(offset, size, version)

    def _record_intervals(self, offset: int, size: int, version: int):
        dat_size = DATA_SHARDS * self.shard_size
        return ec_locate.locate_data(
            self.large_block, self.small_block, dat_size,
            offset, actual_size(size, version))

    def read_needle(self, n: Needle, version: int = 3,
                    remote_reader: Optional[Callable] = None,
                    rs: Optional[ReedSolomon] = None,
                    decoder=None, span_cache=None) -> Needle:
        """Read+verify a needle from local shards, remote shards, or by
        live RS reconstruction of missing intervals.

        remote_reader(shard_id, shard_offset, length) -> bytes|None is
        supplied by the volume server for non-local shards. `decoder`
        (reads.DegradedReadFleet) routes reconstructions to the fused
        batch path; `span_cache` (cache.TieredReadCache) serves repeat
        degraded reads without re-solving.
        """
        blob = self.read_needle_blob(n.id, version, remote_reader, rs,
                                     decoder, span_cache)
        got = Needle.from_bytes(blob, version)
        if n.cookie and got.cookie != n.cookie:
            from seaweedfs_tpu.storage.needle import CookieMismatch
            raise CookieMismatch(
                f"needle {n.id:x}: cookie {n.cookie:08x} != {got.cookie:08x}")
        return got

    def read_needle_blob(self, needle_id: int, version: int = 3,
                         remote_reader: Optional[Callable] = None,
                         rs: Optional[ReedSolomon] = None,
                         decoder=None, span_cache=None) -> bytes:
        """The raw stored record bytes of one needle — the unit the
        tiered read cache stores (Needle.from_bytes CRC-checks it on
        every parse, so a torn cache entry can never serve)."""
        _, size, intervals = self.locate_needle(needle_id, version)
        pieces = []
        for iv in intervals:
            pieces.append(self._read_interval(iv, remote_reader, rs,
                                              decoder, span_cache))
        return b"".join(pieces)

    def _read_interval(self, iv: ec_locate.Interval,
                       remote_reader: Optional[Callable],
                       rs: Optional[ReedSolomon],
                       decoder=None, span_cache=None) -> bytes:
        shard_id, off = iv.to_shard_and_offset(self.large_block, self.small_block)
        s = self.shards.get(shard_id)
        if s is not None:
            err = None
            try:
                data = s.read_at(off, iv.size)
            except (OSError, ValueError) as e:
                # failing disk, or the shard closed by a concurrent
                # unmount: same demotion as a short read — reconstruct
                err, data = e, b""
            if len(data) == iv.size:
                return data
            # short read (e.g. shard truncated by a crashed rebuild)
            # or read error: treat the shard as missing and reconstruct
            # from the others — but COUNT it, and log once per shard,
            # so operators can tell silent-recovery traffic from decay.
            # The log distinguishes truncation from IO errors: they
            # point at different repairs (bad rebuild vs dying disk).
            ReadsShortShardCounter.labels(
                str(self.volume_id), str(shard_id)).inc()
            if shard_id not in self._short_logged:
                self._short_logged.add(shard_id)
                if err is not None:
                    log.warning(
                        "ec volume %d shard %d: local read error at %d "
                        "(%s); serving via reconstruction until repaired",
                        self.volume_id, shard_id, off, err)
                else:
                    log.warning(
                        "ec volume %d shard %d: short local read (%d < "
                        "%d at %d); serving via reconstruction until "
                        "repaired",
                        self.volume_id, shard_id, len(data), iv.size, off)
            return self._recover_interval(shard_id, off, iv.size,
                                          remote_reader, rs, decoder,
                                          span_cache)
        if remote_reader is not None:
            try:
                data = remote_reader(shard_id, off, iv.size)
            # lint: swallow-ok(failure demotes to RS reconstruction, counted by SeaweedFS_reads_degraded_total)
            except Exception:
                data = None
            if data is not None and len(data) == iv.size:
                return data
        return self._recover_interval(shard_id, off, iv.size, remote_reader,
                                      rs, decoder, span_cache)

    def _recover_interval(self, missing_shard: int, off: int, length: int,
                          remote_reader: Optional[Callable],
                          rs: Optional[ReedSolomon],
                          decoder=None, span_cache=None) -> bytes:
        """On-the-fly RS reconstruction of one interval
        (reference store_ec.go:322-376).

        A reconstructed span is served from / published to `span_cache`
        when one is wired, and the solve itself goes to the fused
        `decoder` fleet when enabled, else to the in-place parallel
        fetch + single-row solve fallback."""
        gen = None
        if span_cache is not None:
            key = span_cache.span_key(self.volume_id, missing_shard, off,
                                      length)
            hit = span_cache.get(key)
            if hit is not None:
                if len(hit) == length:
                    return hit
                # torn span file (disk-tier entry truncated by power
                # loss): drop it and reconstruct
                span_cache.drop(key)
            # snapshot before solving: a rebuild/scrub invalidation
            # racing this reconstruction must win (set refuses stale)
            gen = span_cache.generation(key)
        if decoder is not None:
            data = decoder.decode(self, missing_shard, off, length,
                                  remote_reader)
        else:
            data = self._recover_in_place(missing_shard, off, length,
                                          remote_reader, rs)
        if span_cache is not None:
            span_cache.set(key, data, gen=gen)
        return data

    def _recover_in_place(self, missing_shard: int, off: int, length: int,
                          remote_reader: Optional[Callable],
                          rs: Optional[ReedSolomon]) -> bytes:
        """The fleet-less fallback: fetch 10 source rows with the
        shared reader pool (local reads all in parallel, then the
        remote deficit in parallel) and solve the one-row
        reconstruction locally. Byte-identical to the historical
        serial loop — any 10 valid rows produce the same bytes."""
        rs = rs or ReedSolomon()
        pool = _get_recover_pool()
        rows: List[np.ndarray] = []
        ids: List[int] = []
        # snapshot: a concurrent unmount between membership test and
        # element access must degrade the row, not raise KeyError
        shards = dict(self.shards)
        local_futs = [
            (sid, pool.submit(shards[sid].read_at, off, length))
            for sid in range(TOTAL_SHARDS)
            if sid != missing_shard and sid in shards]
        for sid, fut in local_futs:
            try:
                b = fut.result()
            except (OSError, ValueError):  # failing disk / closed by
                b = b""                    # a concurrent unmount
            if len(b) == length and len(ids) < DATA_SHARDS:
                ids.append(sid)
                rows.append(np.frombuffer(b, dtype=np.uint8))
        if len(ids) < DATA_SHARDS and remote_reader is not None:
            remote_sids = [sid for sid in range(TOTAL_SHARDS)
                           if sid != missing_shard and sid not in ids]
            remote_futs = [(sid, pool.submit(remote_reader, sid, off,
                                             length))
                           for sid in remote_sids]
            for sid, fut in remote_futs:
                if len(ids) >= DATA_SHARDS:
                    break
                try:
                    b = fut.result()
                # lint: swallow-ok(a dead peer fails rows, not reads; deficit rows top up below)
                except Exception:
                    b = None
                if b is not None and len(b) == length:
                    ids.append(sid)
                    rows.append(np.frombuffer(b, dtype=np.uint8))
        if len(ids) < DATA_SHARDS:
            raise EcShardNotFound(
                f"vid {self.volume_id} shard {missing_shard}: only "
                f"{len(ids)} shards reachable, need {DATA_SHARDS}")
        # rows were appended local-first: restore canonical sid order so
        # the decode matrix (and its cache key) is deterministic
        order = np.argsort(ids)
        src = np.stack([rows[i] for i in order], axis=0)
        ids = [ids[i] for i in order]
        out = rs.reconstruct_some(ids, [missing_shard], src)
        ReadsDegradedCounter.inc()
        ReadsDecodedBytesCounter.inc(float(length))
        return out[0].tobytes()

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        with self._lock:
            for s in self.shards.values():
                s.close()
            self.shards.clear()
            self._ecx.close()
            self._ecj.close()

    def destroy(self) -> None:
        """Remove all local ec files for this volume."""
        with self._lock:
            for s in list(self.shards.values()):
                s.destroy()
            self.shards.clear()
            self._ecx.close()
            self._ecj.close()
            for ext in (".ecx", ".ecj"):
                p = self.base_name + ext
                if os.path.exists(p):
                    os.remove(p)

    def file_count(self) -> int:
        alive = ~np.isin(self._sizes, [t.TOMBSTONE_SIZE]) & (self._sizes >= 0)
        return int(alive.sum())
