"""Store-level EC operations: the volume server's EC surface.

Functional equivalents of the reference's store_ec.go /
store_ec_delete.go and the per-RPC handlers in
server/volume_grpc_erasure_coding.go:38-400 — generate, rebuild,
mount/unmount, shard reads, EC needle reads with live recovery, decode
back to a normal volume. All take the Store as first arg; the Store
stays EC-agnostic (the ec package plugs into DiskLocation.ec_volumes).
"""

from __future__ import annotations

import os
import struct
from typing import Callable, Dict, Iterable, List, Optional, Sequence

from seaweedfs_tpu.ec import encoder, fleet
from seaweedfs_tpu.ec.ec_volume import EcVolume, EcShardNotFound
from seaweedfs_tpu.ec.shard_bits import TOTAL_SHARDS
from seaweedfs_tpu.ops.rs_code import ReedSolomon
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import StoreEcSecondsHistogram
from seaweedfs_tpu.storage.needle import Needle, NeedleError
from seaweedfs_tpu.storage.store import Store

# An EC call's wall by step; children resolved once at import
# (labels() takes a lock per call).
_STEP_HIST = {step: StoreEcSecondsHistogram.labels(step)
              for step in ("freeze", "generate", "generate_batch",
                           "write_ecx", "locate", "rebuild_batch")}


def _step(step: str, **tags) -> trace.PhaseTimer:
    """Timer of one step of a store EC call (span `store_ec.<step>`)."""
    return trace.PhaseTimer(_STEP_HIST[step], "store_ec." + step, **tags)


def _base_name(directory: str, collection: str, vid: int) -> str:
    name = f"{collection}_{vid}" if collection else str(vid)
    return os.path.join(directory, name)


def _find_ec_base(store: Store, vid: int,
                  collection: Optional[str] = None) -> Optional[str]:
    """Locate the <base>.ecx for a volume across disk locations.

    A mounted EcVolume is authoritative for the collection name; when
    collection is unknown the directories are scanned for any
    [collection_]vid.ecx match (the same discovery rule
    DiskLocation._load_ec_shards uses)."""
    ecv = store.find_ec_volume(vid)
    if ecv is not None and os.path.exists(ecv.base_name + ".ecx"):
        return ecv.base_name
    for loc in store.locations:
        if collection is not None:
            base = _base_name(loc.directory, collection, vid)
            if os.path.exists(base + ".ecx"):
                return base
            continue
        for name in os.listdir(loc.directory):
            if not name.endswith(".ecx"):
                continue
            stem = name[:-len(".ecx")]
            col, _, tail = stem.rpartition("_")
            if tail == str(vid) or (not col and stem == str(vid)):
                return os.path.join(loc.directory, stem)
    return None


def generate_ec_shards(store: Store, vid: int, backend: str = "auto") -> str:
    """VolumeEcShardsGenerate: .dat/.idx -> .ec00-13 + .ecx.

    The volume must exist locally; it is marked read-only first (the
    shell's ec.encode does this cluster-wide before calling in).
    Returns the base name the shard files were written under.
    """
    v = store.find_volume(vid)
    if v is None:
        raise NeedleError(f"volume {vid} not found for ec encode")
    with _step("freeze", volumes=1):
        v.read_only = True
        v.sync()
    base = v.file_name()
    with _step("generate", vid=vid):
        encoder.write_ec_files(base, backend=backend)
        encoder.write_sorted_file_from_idx(base)
    return base


def generate_ec_shards_batch(store: Store, vids: Sequence[int],
                             backend: str = "auto",
                             mesh_cfg: Optional[dict] = None
                             ) -> Dict[int, str]:
    """VolumeEcShardsGenerate for MANY volumes in one fused pass.

    Every volume is frozen (read-only + sync) up front, then ONE
    scheduler packs chunks from all of them into shared RS dispatches:
    with `mesh_cfg` (the volume server's -ec.mesh* knobs) the pass
    rides the unified pod-scale mesh scheduler
    (parallel/mesh_fleet.pod_write_ec_files, which falls back to the
    per-device fleet ladder on any MeshError); without it, the host
    fleet scheduler (ec/fleet.py). Shard bytes are identical to
    calling generate_ec_shards per volume either way. Returns
    {vid: base_name}.
    """
    vols = []
    for vid in vids:  # validate the whole list BEFORE freezing any —
        v = store.find_volume(vid)  # a bad vid must not strand earlier
        if v is None:               # volumes read-only with no shards
            raise NeedleError(f"volume {vid} not found for ec encode")
        vols.append((vid, v))
    bases: Dict[int, str] = {}
    with _step("freeze", volumes=len(vols)):
        for vid, v in vols:
            v.read_only = True
            v.sync()
            bases[vid] = v.file_name()
    with _step("generate_batch", volumes=len(bases)):
        mesh_fleet = fleet.mesh_fleet_or_none() \
            if mesh_cfg is not None else None
        if mesh_fleet is not None:
            mesh_fleet.pod_write_ec_files(list(bases.values()),
                                          backend=backend, **mesh_cfg)
        else:
            fleet.fleet_write_ec_files(list(bases.values()),
                                       backend=backend)
        with _step("write_ecx"):
            for base in bases.values():
                encoder.write_sorted_file_from_idx(base)
    return bases


def rebuild_ec_shards_batch(store: Store, vids: Sequence[int],
                            collection: Optional[str] = None,
                            backend: str = "auto") -> Dict[int, List[int]]:
    """VolumeEcShardsRebuild for MANY volumes in one pass of the fleet
    scheduler (ec/fleet.py): volumes that miss the same shards and hold
    the same survivors share a decode matrix and their spans fuse into
    shared RS dispatches. Every volume is located BEFORE any file is
    written. Shard bytes are identical to `encoder.rebuild_ec_files`
    per volume (the serial reference). Returns {vid: rebuilt shard
    ids}."""
    bases: Dict[int, str] = {}
    with _step("locate", volumes=len(vids)):
        for vid in vids:
            base = _find_ec_base(store, vid, collection)
            if base is None:
                raise EcShardNotFound(
                    f"no local ec files for volume {vid}")
            bases[vid] = base
    with _step("rebuild_batch", volumes=len(bases)):
        rebuilt = fleet.fleet_rebuild_ec_files(list(bases.values()),
                                               backend=backend)
    return {vid: rebuilt[base] for vid, base in bases.items()}


def mount_ec_shards(store: Store, vid: int, collection: str,
                    shard_ids: Iterable[int]) -> EcVolume:
    """VolumeEcShardsMount: open shard files and register the EcVolume."""
    base = _find_ec_base(store, vid, collection)
    if base is None:
        raise EcShardNotFound(f"volume {vid}: no .ecx on any disk location")
    loc = next(l for l in store.locations
               if os.path.dirname(base) == l.directory)
    ecv = loc.ec_volumes.get(vid)
    if ecv is None:
        ecv = EcVolume(loc.directory, collection, vid)
        loc.ec_volumes[vid] = ecv
    for sid in shard_ids:
        ecv.mount_shard(sid)
    return ecv


def unmount_ec_shards(store: Store, vid: int,
                      shard_ids: Iterable[int]) -> None:
    """VolumeEcShardsUnmount; drops the EcVolume when no shards remain."""
    ecv = store.find_ec_volume(vid)
    if ecv is None:
        return
    for sid in shard_ids:
        ecv.unmount_shard(sid)
    if not ecv.shards:
        loc = store.location_of(vid)
        ecv.close()
        if loc is not None:
            loc.ec_volumes.pop(vid, None)


def delete_ec_shards(store: Store, vid: int, collection: Optional[str] = None,
                     shard_ids: Iterable[int] = ()) -> None:
    """VolumeEcShardsDelete: remove shard files; when none remain, the
    .ecx/.ecj go too (reference volume_grpc_erasure_coding.go:136-210)."""
    base = _find_ec_base(store, vid, collection)
    if base is None:
        return
    ecv = store.find_ec_volume(vid)
    for sid in shard_ids:
        if ecv is not None:
            ecv.unmount_shard(sid)
        p = encoder.shard_file_name(base, sid)
        if os.path.exists(p):
            os.remove(p)
    if not any(os.path.exists(encoder.shard_file_name(base, i))
               for i in range(TOTAL_SHARDS)):
        loc = next(l for l in store.locations
                   if os.path.dirname(base) == l.directory)
        if ecv is not None:
            ecv.close()
            loc.ec_volumes.pop(vid, None)
        for ext in (".ecx", ".ecj"):
            if os.path.exists(base + ext):
                os.remove(base + ext)


def read_ec_shard(store: Store, vid: int, shard_id: int, offset: int,
                  length: int) -> bytes:
    """VolumeEcShardRead: raw bytes of one local shard (serves remote
    peers' interval reads)."""
    ecv = store.find_ec_volume(vid)
    if ecv is None:
        raise EcShardNotFound(f"ec volume {vid} not mounted")
    shard = ecv.shards.get(shard_id)
    if shard is None:
        raise EcShardNotFound(f"ec volume {vid} shard {shard_id} not local")
    return shard.read_at(offset, length)


def read_ec_needle(store: Store, vid: int, n: Needle,
                   remote_reader: Optional[Callable] = None,
                   rs: Optional[ReedSolomon] = None,
                   cache=None, decoder=None,
                   version: int = 3) -> Needle:
    """ReadEcShardNeedle: cookie-checked needle read over shards, with
    remote fan-out and on-the-fly RS recovery (store_ec.go:122-262).

    With a `cache` (cache.TieredReadCache) the whole stored record
    rides the needle-keyed tier: repeat reads of a hot needle — healthy
    or degraded — cost one cache hit and a CRC-checked parse, and
    concurrent misses single-flight so one reconstruction serves them
    all. `decoder` (reads.DegradedReadFleet) fuses any reconstruction
    the read does need into batched RS dispatches.
    """
    ecv = store.find_ec_volume(vid)
    if ecv is None:
        raise EcShardNotFound(f"ec volume {vid} not mounted")
    if cache is None:
        return ecv.read_needle(n, version, remote_reader=remote_reader,
                               rs=rs, decoder=decoder)
    sp = trace.span("reads.ec_needle", vid=vid) \
        if trace.is_enabled() else trace.NOOP
    with sp:
        key = cache.needle_key(vid, n.id)
        blob = cache.get(key)
        if blob is None:
            with cache.single_flight(key) as leader:
                if not leader:
                    blob = cache.get(key)  # the leader's result
                if blob is None:
                    # gen snapshot BEFORE the read: if the key or its
                    # volume is invalidated while we reconstruct
                    # (delete, scrub repair), set() refuses the blob
                    gen = cache.generation(key)
                    blob = ecv.read_needle_blob(
                        n.id, version, remote_reader, rs, decoder,
                        span_cache=cache)
                    cache.set(key, blob, gen=gen)
        try:
            got = Needle.from_bytes(blob, version)
        except (NeedleError, ValueError, IndexError, struct.error):
            # poisoned cache data (a file torn by power loss before
            # restart): a bad NEEDLE entry arrives as a cache hit; a
            # bad SPAN entry poisons a freshly-assembled blob. Either
            # way: drop the needle key AND the volume's span entries,
            # then retry once straight from the shards (span cache
            # bypassed). A retry failure is true shard corruption and
            # propagates.
            cache.drop(key)
            cache.drop_spans(vid)
            gen = cache.generation(key)
            blob = ecv.read_needle_blob(n.id, version, remote_reader,
                                        rs, decoder, span_cache=None)
            cache.set(key, blob, gen=gen)
            got = Needle.from_bytes(blob, version)
    if n.cookie and got.cookie != n.cookie:
        from seaweedfs_tpu.storage.needle import CookieMismatch
        raise CookieMismatch(
            f"needle {n.id:x}: cookie {n.cookie:08x} != {got.cookie:08x}")
    return got


def delete_ec_needle(store: Store, vid: int, n: Needle,
                     cache=None) -> None:
    """Tombstone in .ecx + journal to .ecj (store_ec_delete.go);
    drops the needle's cached entries so a delete is never masked."""
    ecv = store.find_ec_volume(vid)
    if ecv is None:
        raise EcShardNotFound(f"ec volume {vid} not mounted")
    ecv.delete_needle(n.id)
    if cache is not None:
        cache.invalidate(vid, n.id, reason="delete")


def scrub_ec_volume(store: Store, vid: int, backend: str = "auto",
                    mbps: float = 0.0):
    """Targeted integrity scrub of ONE mounted EC volume: needle sweep,
    stripe verify, and (when damaged) quarantine + reconstruction —
    the store-level form of the daemon's whole-store pass, for ad-hoc
    operator checks. Returns the scrub PassResult."""
    from seaweedfs_tpu.scrub import ScrubDaemon
    if store.find_ec_volume(vid) is None:
        raise EcShardNotFound(f"ec volume {vid} not mounted")
    # export_lag=False: a throwaway targeted pass must not hijack the
    # process-global scan-lag gauge from the server's own daemon
    daemon = ScrubDaemon(store, backend=backend, mbps=mbps,
                         export_lag=False)
    return daemon.run_pass(volume_ids=[vid])


def ec_shards_to_volume(store: Store, vid: int, collection: str = "",
                        backend: str = "auto",
                        large_block: int = encoder.LARGE_BLOCK_SIZE,
                        small_block: int = encoder.SMALL_BLOCK_SIZE) -> None:
    """VolumeEcShardsToVolume: decode .ec00-09 (+.ecx/.ecj) back into a
    loadable .dat/.idx volume (reference
    volume_grpc_erasure_coding.go:360-400 + ec_decoder.go)."""
    if store.find_ec_volume(vid) is not None:
        raise EcShardNotFound(
            f"volume {vid}: unmount ec shards before decoding back "
            "(a mounted EcVolume would serve stale reads)")
    base = _find_ec_base(store, vid, collection or None)
    if base is None:
        raise EcShardNotFound(f"volume {vid}: no .ecx to decode from")
    loc = next(l for l in store.locations
               if os.path.dirname(base) == l.directory)
    stem = os.path.basename(base)
    collection = stem.rsplit("_", 1)[0] if "_" in stem else ""
    # only the data shards are read back; don't waste RS compute
    # regenerating missing parity
    encoder.rebuild_ec_files(base, backend=backend,
                             wanted=list(range(encoder.DATA_SHARDS)))
    dat_size = encoder.find_dat_file_size(base)
    encoder.write_dat_file(base, dat_size, backend=backend,
                           large_block=large_block, small_block=small_block)
    encoder.write_idx_file_from_ec_index(base)
    from seaweedfs_tpu.storage.volume import Volume
    with loc._lock:
        v = Volume(loc.directory, collection, vid, create_if_missing=False,
                   needle_map_kind=loc.needle_map_kind)
        loc.volumes[vid] = v
    store.new_volumes.append(store.volume_info(v))
