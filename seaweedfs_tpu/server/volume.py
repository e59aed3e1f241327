"""Volume server: the dataplane node.

HTTP serves the public blob path (GET/POST/DELETE /<vid>,<fid>); gRPC
serves the admin plane (allocate, vacuum, copy, the EC lifecycle); a
background thread streams heartbeats to the master leader.

Reference: weed/server/volume_server.go, volume_server_handlers_*.go,
volume_grpc_*.go, volume_grpc_client_to_master.go.
"""

from __future__ import annotations

import gzip
import json
import os
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple
from urllib.parse import parse_qs

import grpc

from seaweedfs_tpu import rpc
from seaweedfs_tpu.resilience import breaker as _breaker
from seaweedfs_tpu.resilience import deadline as _deadline
from seaweedfs_tpu.resilience import failpoint as _failpoint
from seaweedfs_tpu.util import http_client, wlog
from seaweedfs_tpu.util.http_server import (FastHandler, ServeConfig,
                                            make_http_server)
from seaweedfs_tpu.util.throttler import Throttler
from seaweedfs_tpu.ec import store_ec
from seaweedfs_tpu.ec.ec_volume import EcShardNotFound
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.ec.shard_bits import DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.operation.file_id import parse_fid
from seaweedfs_tpu.pb import (master_pb2, master_stub, volume_server_pb2,
                              volume_stub)
from seaweedfs_tpu.server import convert
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage import vacuum as vacuum_mod
from seaweedfs_tpu.storage import volume_backup, volume_tier
from seaweedfs_tpu.scrub import ScrubDaemon
from seaweedfs_tpu.storage.backend import BackendError
from seaweedfs_tpu.storage.needle import (FLAG_IS_CHUNK_MANIFEST,
                                          FLAG_IS_COMPRESSED,
                                          CookieMismatch,
                                          DataCorruptionError, Needle,
                                          NeedleError)
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.superblock import TTL
from seaweedfs_tpu.storage.volume import VolumeError

log = wlog.logger("volume")

COPY_CHUNK = 1 << 20
# EC shard-location freshness is tiered by how complete the cached view
# is (reference storage/store_ec.go:221-231): a sparse view (fewer than
# DATA_SHARDS known) re-asks the master after 11s, a readable-but-
# incomplete view after 7m, a complete view only after 37m
EC_REFRESH_SPARSE_S = 11.0
EC_REFRESH_PARTIAL_S = 7 * 60.0
EC_REFRESH_FULL_S = 37 * 60.0
# Replica-location freshness: replica sets move on volume.fix.replication
# / rebalance, so the window stays short; any replica POST failure
# forgets the vid immediately (same invalidate-on-failure discipline as
# _ec_locations)
REPLICA_REFRESH_S = 30.0
# A read of shard bytes from their holder (VolumeEcShardRead) fails after
# this: a hung peer must fail the row, not pin its caller
REMOTE_SHARD_READ_S = 15.0
# A scrub verifier's probe of an earlier holder, and its wait for a
# holder's rebuild of a condemned shard
SCRUB_PROBE_S = 10.0
SCRUB_REPAIR_S = 300.0


class VolumeServer:
    def __init__(self, master_url: str, directories: List[str],
                 ip: str = "127.0.0.1", port: int = 8080,
                 public_url: str = "", data_center: str = "",
                 rack: str = "", max_volume_counts: Optional[List[int]] = None,
                 pulse_seconds: float = 5.0, ec_encoder: str = "auto",
                 compaction_mbps: float = 0.0,
                 storage_backends: Optional[dict] = None,
                 needle_map_kind: str = "memory",
                 scrub_mbps: float = 0.0,
                 scrub_interval_s: float = 0.0,
                 cache_size_mb: int = 0,
                 cache_dir: Optional[str] = None,
                 degraded_fleet: bool = True,
                 degraded_batch_ms: float = 2.0,
                 replicate_parallel: int = 8,
                 hedge_reads: bool = False,
                 hedge_delay_ms: float = 10.0,
                 heat_track: bool = False,
                 heat_window_s: float = 60.0,
                 ec_mesh: bool = False,
                 ec_mesh_min_volumes: int = 0,
                 ec_mesh_bucket_mb: int = 32,
                 ec_mesh_timeout_s: float = 30.0,
                 serve: Optional[ServeConfig] = None):
        if storage_backends:
            # cloud-tier targets, e.g. {"s3.default": {...}} (reference
            # master.toml [storage.backend.s3.default])
            from seaweedfs_tpu.storage import backend as _bk
            _bk.load_configuration(storage_backends)
        self.master_url = master_url
        # the master this server last heartbeated successfully (the
        # leader); master_url may be a comma-separated candidate list,
        # so lookups must dial this, never the raw flag value
        self.current_master = master_url.split(",")[0].strip()
        self.ip = ip
        self.port = port
        self.data_center = data_center
        self.rack = rack
        self.pulse_seconds = pulse_seconds
        self.ec_encoder = ec_encoder
        # -ec.mesh* knobs for the unified pod-scale scheduler
        # (parallel/mesh_fleet). None — not merely empty — when
        # disabled, so the default path never imports the mesh module
        # or queries jax devices
        # (test_perf_gates.test_mesh_disabled_overhead)
        self.ec_mesh_cfg = None
        if ec_mesh:
            self.ec_mesh_cfg = {
                "min_volumes": ec_mesh_min_volumes,
                "bucket_mb": ec_mesh_bucket_mb,
                "timeout_s": ec_mesh_timeout_s,
            }
        self.compaction_mbps = compaction_mbps
        self.store = Store(directories, max_volume_counts, ip=ip, port=port,
                           public_url=public_url,
                           needle_map_kind=needle_map_kind)
        # tiered read cache (-cache.sizeMB/-cache.dir): absent — not
        # merely empty — unless sized, so the disabled read path never
        # pays a lookup (test_perf_gates.test_cache_disabled_overhead)
        self.read_cache = None
        if cache_size_mb > 0:
            from seaweedfs_tpu.cache import TieredReadCache
            self.read_cache = TieredReadCache(
                cache_size_mb << 20,
                disk_dir=os.path.join(cache_dir, f"rc{port}")
                if cache_dir else None)
        # degraded-read decode fleet: fuses concurrent on-the-fly RS
        # reconstructions into [B, 10, span] dispatches. Constructing
        # it spawns nothing; threads appear on the first degraded read
        # (test_perf_gates.test_degraded_decode_disabled_overhead).
        self.degraded = None
        if degraded_fleet:
            from seaweedfs_tpu.reads import DegradedReadFleet
            self.degraded = DegradedReadFleet(
                backend=ec_encoder,
                batch_window_s=degraded_batch_ms / 1000.0,
                use_mesh=ec_mesh)
        # background integrity scrub: costs nothing (no thread, no IO)
        # until started — by RPC, by the master's staggered scheduler,
        # or at boot when -scrub.intervalSeconds is set
        self.scrub = ScrubDaemon(
            self.store, mbps=scrub_mbps, backend=ec_encoder,
            interval_s=scrub_interval_s,
            replica_fetch=self._fetch_needle_from_replica,
            on_repair=self._invalidate_volume_cache,
            mesh_cfg=self.ec_mesh_cfg, peers=_ScrubPeers(self))
        self.scrub_interval_s = scrub_interval_s
        self.volume_size_limit = 30 << 30
        self.compact_states: Dict[int, vacuum_mod.CompactState] = {}
        self._ec_locations: Dict[int, Tuple[float, Dict[int, List[str]]]] = {}
        # replica fan-out (-replicate.parallel): all replica POSTs for
        # one write go out concurrently on this shared pool. The pool
        # spawns no threads until the first multi-replica fan-out
        # (single-replica placements run inline), and replica URLs are
        # cached per vid instead of asking the master on EVERY
        # replicated write
        from seaweedfs_tpu.util.fanout import FanOutPool
        self._replicate_pool = FanOutPool(
            max(1, replicate_parallel), f"replicate-{port}")
        self._replica_urls: Dict[int, Tuple[float, List[str]]] = {}
        # hedged remote shard reads (-resilience.hedge): absent unless
        # enabled; a constructed Hedger spawns nothing until its first
        # multi-candidate fetch (resilience house rule)
        self.hedger = None
        if hedge_reads:
            from seaweedfs_tpu.resilience import Hedger
            self.hedger = Hedger(
                delay_floor_s=max(hedge_delay_ms, 0.1) / 1000.0,
                name=f"hedge-volume-{port}")
        # read-path heat telemetry (-heat.track): absent — not merely
        # idle — unless enabled, so the disabled read path pays one
        # None check (the lifecycle subsystem's measurement half)
        from seaweedfs_tpu.stats.heat import make_tracker
        self.heat = make_tracker(heat_track, window_s=heat_window_s)
        # -serve.* config: the async selector core (and its zero-copy
        # sendfile GET path) only exists when asked for — the default
        # server never imports util/async_server
        # (test_perf_gates.test_serve_async_disabled_overhead)
        self.serve = serve or ServeConfig()
        self._grpc_server = None
        self._http_server = None
        self._http_thread = None
        self._hb_thread = None
        self._hb_call = None
        self._hb_wake = threading.Event()
        self._stopping = False

    # -- lifecycle -----------------------------------------------------------

    @property
    def url(self) -> str:
        return f"{self.ip}:{self.port}"

    def start(self) -> None:
        if self.ec_encoder == "jax":
            # device codec: place the persistent compile cache before
            # the first dispatch compiles anything (one owner, see
            # util/compile_cache.py). Host codecs never import jax.
            from seaweedfs_tpu.util import compile_cache
            compile_cache.configure()
        handler = rpc.generic_handler(
            volume_server_pb2, "VolumeServer", self)
        self._grpc_server = rpc.make_server(
            f"{self.ip}:{self.port + rpc.GRPC_PORT_OFFSET}", [handler])
        self._http_server = make_http_server(
            (self.ip, self.port), _make_http_handler(self),
            role="volume", serve=self.serve)
        # lint: thread-ok(listener thread; ingress wrappers mint request context)
        self._http_thread = threading.Thread(
            target=self._http_server.serve_forever,
            name=f"volume-http-{self.port}", daemon=True)
        self._http_thread.start()
        # lint: thread-ok(listener thread; ingress wrappers mint request context)
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, name=f"heartbeat-{self.port}",
            daemon=True)
        self._hb_thread.start()
        if self.scrub_interval_s > 0:
            self.scrub.start()
        log.info("volume server %s:%d started (grpc :%d, dirs %s)",
                 self.ip, self.port, self.port + rpc.GRPC_PORT_OFFSET,
                 [loc.directory for loc in self.store.locations])

    def stop(self) -> None:
        log.info("volume server %s:%d stopping", self.ip, self.port)
        self._stopping = True
        if self.heat is not None:
            self.heat.close()
        if self.degraded is not None:
            self.degraded.stop()
        self.scrub.stop()
        self._hb_wake.set()
        if self._hb_call is not None:
            self._hb_call.cancel()
        if self._http_server:
            self._http_server.shutdown()
            self._http_server.server_close()
        if self._grpc_server:
            self._grpc_server.stop(grace=0.2)
        # drain in-flight replica fan-outs before the store closes
        # (util/grace shutdown contract)
        self._replicate_pool.stop()
        self.store.close()

    # -- heartbeat ------------------------------------------------------------

    def _heartbeat_gen(self):
        while not self._stopping:
            hb = self.store.collect_heartbeat()
            if self.heat is not None:
                # heat summary rides the heartbeat: the master's
                # topology aggregates every server's window reads +
                # decayed EWMA into the cluster heat map the lifecycle
                # policy engine decides from. Absent (not empty) when
                # -heat.track is off, so the disabled wire format is
                # byte-identical to pre-lifecycle heartbeats.
                hb["volume_heats"] = self.heat.summary()
            yield convert.heartbeat_to_pb(hb, self.data_center, self.rack)
            self._hb_wake.wait(timeout=self.pulse_seconds)
            self._hb_wake.clear()

    def _heartbeat_loop(self) -> None:
        """Keep one bidi heartbeat stream to the master LEADER.

        master_url may list several masters (comma-separated); a
        follower answers with the leader's address and the loop redials
        it (reference volume_grpc_client_to_master.go:50-95 follows
        HeartbeatResponse.leader the same way).
        """
        candidates = [m.strip() for m in self.master_url.split(",")
                      if m.strip()]
        target = candidates[0]
        rotate = 0
        while not self._stopping:
            redirect = None
            try:
                stub = master_stub(target)
                self._hb_call = stub.SendHeartbeat(self._heartbeat_gen())
                connected = False
                for resp in self._hb_call:
                    if resp.leader and resp.leader != target:
                        redirect = resp.leader
                        log.info("master %s redirects heartbeat to "
                                 "leader %s", target, redirect)
                        self._hb_call.cancel()
                        break
                    if not connected:
                        connected = True
                        self.current_master = target
                        log.info("heartbeat stream to master %s established",
                                 target)
                    if resp.volume_size_limit:
                        self.volume_size_limit = resp.volume_size_limit
                    if self._stopping:
                        return
            except grpc.RpcError as e:
                if self._stopping:
                    return
                log.warning("heartbeat stream to master %s broken (%s); "
                            "reconnecting", target,
                            getattr(e, "code", lambda: e)())
                time.sleep(min(self.pulse_seconds, 1.0))
            if self._stopping:
                return
            if redirect:
                target = redirect
            else:
                # rotate through the configured masters on plain breaks
                # — with a pause, so a leaderless election window
                # doesn't turn into a tight redial spin
                rotate += 1
                target = candidates[rotate % len(candidates)]
                self._hb_wake.wait(timeout=min(self.pulse_seconds, 1.0))
                self._hb_wake.clear()

    def trigger_heartbeat(self) -> None:
        """Push a delta heartbeat now instead of waiting out the pulse."""
        self._hb_wake.set()

    # -- gRPC: volume lifecycle ------------------------------------------------

    def AllocateVolume(self, request, context):
        self.store.add_volume(request.volume_id, request.collection,
                              replica_placement=request.replication or "000",
                              ttl=request.ttl)
        self.trigger_heartbeat()
        return volume_server_pb2.AllocateVolumeResponse()

    def VolumeDelete(self, request, context):
        self.store.delete_volume(request.volume_id)
        self._forget_heat(request.volume_id)
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeDeleteResponse()

    def VolumeMarkReadonly(self, request, context):
        if not self.store.mark_volume_readonly(request.volume_id):
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeMarkReadonlyResponse()

    def VolumeMarkWritable(self, request, context):
        try:
            found = self.store.mark_volume_writable(request.volume_id)
        except VolumeError as e:  # cloud-tiered volumes stay sealed
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        if not found:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeMarkWritableResponse()

    def VolumeMount(self, request, context):
        vid = request.volume_id
        if self.store.find_volume(vid) is None:
            found = False
            for loc in self.store.locations:
                for name in os.listdir(loc.directory):
                    if not name.endswith(".dat"):
                        continue
                    stem = name[:-len(".dat")]
                    col, _, tail = stem.rpartition("_")
                    if tail == str(vid) or (not col and stem == str(vid)):
                        loc.add_volume(vid, col)
                        found = True
                        break
                if found:
                    break
            if not found:
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"no .dat for volume {vid} on any disk")
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeMountResponse()

    def VolumeUnmount(self, request, context):
        vid = request.volume_id
        for loc in self.store.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                v.close()
                loc.volumes.pop(vid, None)
        self._forget_heat(vid)
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeUnmountResponse()

    def DeleteCollection(self, request, context):
        for loc in self.store.locations:
            for vid, v in list(loc.volumes.items()):
                if v.collection == request.collection:
                    loc.delete_volume(vid)
                    self._forget_heat(vid)
            for vid, ecv in list(loc.ec_volumes.items()):
                if ecv.collection == request.collection:
                    ecv.destroy()
                    loc.ec_volumes.pop(vid, None)
                    self._forget_heat(vid)
        self.trigger_heartbeat()
        return volume_server_pb2.DeleteCollectionResponse()

    def _forget_heat(self, vid: int) -> None:
        """Heat hygiene on volume departure/conversion: without this a
        dead vid's SeaweedFS_volume_heat{vid} child and counters
        linger forever (unbounded label growth)."""
        if self.heat is not None:
            self.heat.forget(vid)

    def ReadVolumeFileStatus(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        base = v.file_name()
        return volume_server_pb2.ReadVolumeFileStatusResponse(
            volume_id=v.id,
            idx_file_size=os.path.getsize(base + ".idx"),
            dat_file_size=os.path.getsize(base + ".dat"),
            idx_file_timestamp_seconds=int(os.path.getmtime(base + ".idx")),
            dat_file_timestamp_seconds=int(os.path.getmtime(base + ".dat")),
            file_count=v.file_count,
            compaction_revision=v.super_block.compaction_revision,
            collection=v.collection)

    # -- gRPC: vacuum ----------------------------------------------------------

    def VacuumVolumeCheck(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        return volume_server_pb2.VacuumVolumeCheckResponse(
            garbage_ratio=v.garbage_ratio())

    def VacuumVolumeCompact(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        self.compact_states[v.id] = vacuum_mod.compact(
            v, preallocate=request.preallocate,
            compaction_mbps=self.compaction_mbps)
        return volume_server_pb2.VacuumVolumeCompactResponse()

    def VacuumVolumeCommit(self, request, context):
        v = self.store.find_volume(request.volume_id)
        state = self.compact_states.pop(request.volume_id, None)
        if v is None or state is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          f"volume {request.volume_id}: no pending compaction")
        vacuum_mod.commit_compact(v, state)
        return volume_server_pb2.VacuumVolumeCommitResponse(
            is_read_only=v.read_only)

    def VacuumVolumeCleanup(self, request, context):
        v = self.store.find_volume(request.volume_id)
        self.compact_states.pop(request.volume_id, None)
        if v is not None:
            for ext in (".cpd", ".cpx"):
                p = v.file_name() + ext
                if os.path.exists(p):
                    os.remove(p)
        return volume_server_pb2.VacuumVolumeCleanupResponse()

    # -- gRPC: batch delete ----------------------------------------------------

    def BatchDelete(self, request, context):
        results = []
        for fid in request.file_ids:
            try:
                f = parse_fid(fid)
            except ValueError as e:
                results.append(volume_server_pb2.DeleteResult(
                    file_id=fid, status=400, error=str(e)))
                continue
            n = Needle(id=f.key, cookie=f.cookie)
            try:
                if not request.skip_cookie_check:
                    got = self._read_needle(f.volume_id, n)
                    if got.cookie != f.cookie:
                        raise CookieMismatch(f"cookie mismatch on {fid}")
                    if got.is_chunk_manifest:
                        # cascading here could recurse through this very
                        # RPC; refuse like the reference
                        # (volume_grpc_batch_delete.go:62-69)
                        results.append(volume_server_pb2.DeleteResult(
                            file_id=fid, status=406,
                            error="ChunkManifest: not allowed in batch "
                                  "delete mode."))
                        continue
                # replicated like the HTTP DELETE path, so the needle
                # disappears from every replica, not just this server
                size = self.replicated_delete(f.volume_id, n)
                results.append(volume_server_pb2.DeleteResult(
                    file_id=fid, status=202, size=size))
            except CookieMismatch as e:
                results.append(volume_server_pb2.DeleteResult(
                    file_id=fid, status=403, error=str(e)))
            except (NeedleError, EcShardNotFound) as e:
                results.append(volume_server_pb2.DeleteResult(
                    file_id=fid, status=404, error=str(e)))
        return volume_server_pb2.BatchDeleteResponse(results=results)

    # -- gRPC: query (S3 Select-ish) -------------------------------------------

    def Query(self, request, context):
        """Scan stored JSON documents: filter + project, one stripe per
        file id (reference server/volume_grpc_query.go:12-76)."""
        from seaweedfs_tpu.query import Query as JQuery, query_json_lines
        q = JQuery(field=request.filter.field,
                   op=request.filter.operand,
                   value=request.filter.value)
        for fid in request.from_file_ids:
            try:
                f = parse_fid(fid)
            except ValueError as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
            n = Needle(id=f.key, cookie=f.cookie)
            try:
                got = self._read_needle(f.volume_id, n)
            except (NeedleError, EcShardNotFound, CookieMismatch) as e:
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"{fid}: {e}")
            data = got.data
            if got.is_compressed:
                data = gzip.decompress(data)
            records = b"".join(
                json.dumps(rec).encode() + b"\n"
                for rec in query_json_lines(
                    data, list(request.selections), q))
            yield volume_server_pb2.QueriedStripe(records=records)

    # -- gRPC: replica copy ----------------------------------------------------

    def CopyFile(self, request, context):
        path = self._file_path_for_copy(request)
        if path is None or not os.path.exists(path):
            if request.ignore_source_file_not_found:
                return
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"no file for vid={request.volume_id} "
                          f"ext={request.ext}")
        stop = request.stop_offset or os.path.getsize(path)
        throttler = Throttler(self.compaction_mbps)
        with open(path, "rb") as f:
            sent = 0
            while sent < stop:
                chunk = f.read(min(COPY_CHUNK, stop - sent))
                if not chunk:
                    break
                sent += len(chunk)
                throttler.maybe_slowdown(len(chunk))
                yield volume_server_pb2.CopyFileResponse(file_content=chunk)

    def _file_path_for_copy(self, request) -> Optional[str]:
        vid, ext = request.volume_id, request.ext
        if request.is_ec_volume:
            base = store_ec._find_ec_base(self.store, vid,
                                          request.collection or None)
            return base + ext if base else None
        v = self.store.find_volume(vid)
        return v.file_name() + ext if v else None

    def VolumeCopy(self, request, context):
        """Pull a whole volume (.dat + .idx) from source_data_node and
        mount it (reference server/volume_grpc_copy.go)."""
        vid = request.volume_id
        if self.store.find_volume(vid) is not None:
            context.abort(grpc.StatusCode.ALREADY_EXISTS,
                          f"volume {vid} already exists")
        src = volume_stub(request.source_data_node)
        status = src.ReadVolumeFileStatus(
            volume_server_pb2.ReadVolumeFileStatusRequest(volume_id=vid))
        loc = next((l for l in self.store.locations if l.has_free_slot()),
                   None)
        if loc is None:
            context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, "no free slot")
        base = store_ec._base_name(loc.directory, status.collection, vid)
        try:
            for ext in (".idx", ".dat"):
                self._pull_file(src, vid, ext, base + ext,
                                collection=status.collection)
        except grpc.RpcError:
            for ext in (".idx", ".dat"):
                if os.path.exists(base + ext):
                    os.remove(base + ext)
            raise
        loc.add_volume(vid, status.collection)
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeCopyResponse(
            last_append_at_ns=time.time_ns())

    def _pull_file(self, src_stub, vid: int, ext: str, dest_path: str,
                   collection: str = "", is_ec: bool = False,
                   ignore_missing: bool = False) -> None:
        tmp = dest_path + ".copying"
        with open(tmp, "wb") as f:
            for resp in src_stub.CopyFile(volume_server_pb2.CopyFileRequest(
                    volume_id=vid, ext=ext, collection=collection,
                    is_ec_volume=is_ec,
                    ignore_source_file_not_found=ignore_missing)):
                f.write(resp.file_content)
        os.replace(tmp, dest_path)

    # -- gRPC: sync status / incremental copy / tail ---------------------------

    def VolumeSyncStatus(self, request, context):
        """Handshake for followers (reference volume_backup.go:19-33)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        st = volume_backup.sync_status(v)
        return volume_server_pb2.VolumeSyncStatusResponse(
            volume_id=st["volume_id"], collection=st["collection"],
            replication=st["replication"], ttl=st["ttl"],
            tail_offset=st["tail_offset"],
            compact_revision=st["compact_revision"],
            idx_file_size=st["idx_file_size"])

    def VolumeIncrementalCopy(self, request, context):
        """Stream raw .dat bytes appended after since_ns
        (reference server/volume_grpc_copy_incremental.go)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        offset, is_last = volume_backup.binary_search_by_append_at_ns(
            v, request.since_ns)
        if is_last:
            return
        for chunk in volume_backup.read_dat_range(v, offset):
            yield volume_server_pb2.VolumeIncrementalCopyResponse(
                file_content=chunk)

    def VolumeTailSender(self, request, context):
        """Stream needles appended after since_ns; keep following until
        the tail stays quiet for idle_timeout_seconds (0 = follow
        forever; reference volume_grpc_tail.go:17-64)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        last_ns = request.since_ns
        draining = request.idle_timeout_seconds
        while True:
            if not context.is_active():
                # client went away: don't pin a gRPC worker thread
                # forever on an idle follow-mode stream
                return
            progressed = False
            offset, is_last = volume_backup.binary_search_by_append_at_ns(
                v, last_ns)
            if not is_last:
                for off, n in volume_backup.scan_dat_from(v, offset):
                    blob = n.to_bytes(v.version)
                    yield volume_server_pb2.VolumeTailSenderResponse(
                        needle_header=blob[:t.NEEDLE_HEADER_SIZE],
                        needle_body=blob[t.NEEDLE_HEADER_SIZE:])
                    if n.append_at_ns > last_ns:
                        last_ns = n.append_at_ns
                        progressed = True
            if request.idle_timeout_seconds == 0:
                time.sleep(1)
                continue
            if progressed:
                draining = request.idle_timeout_seconds
            else:
                draining -= 1
                if draining <= 0:
                    yield volume_server_pb2.VolumeTailSenderResponse(
                        is_last_chunk=True)
                    return
            time.sleep(1)

    def VolumeTailReceiver(self, request, context):
        """Pull a tail stream from source_volume_server and replay it
        into the local replica (reference volume_grpc_tail.go:80-94)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        src = volume_stub(request.source_volume_server)
        for resp in src.VolumeTailSender(
                volume_server_pb2.VolumeTailSenderRequest(
                    volume_id=request.volume_id,
                    since_ns=request.since_ns,
                    idle_timeout_seconds=request.idle_timeout_seconds)):
            if resp.is_last_chunk:
                break
            blob = bytes(resp.needle_header) + bytes(resp.needle_body)
            n = Needle.from_bytes(blob, v.version, check_crc=False)
            if len(n.data) == 0:
                v.delete_needle(n)
            else:
                v.write_needle(n)
        return volume_server_pb2.VolumeTailReceiverResponse()

    # -- gRPC: cloud tier ------------------------------------------------------

    def VolumeTierMoveDatToRemote(self, request, context):
        """Upload a sealed volume's bulk bytes to the named storage
        backend (reference volume_grpc_tier_upload.go). A normal
        volume moves its .dat; an erasure-coded vid moves this
        server's .ecNN shard files instead (the lifecycle engine's
        WARM -> COLD leg) — the .idx/.ecx index always stays local."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            ecv = self.store.find_ec_volume(request.volume_id)
            if ecv is None:
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"volume {request.volume_id} not found")
            try:
                total = volume_tier.move_ec_shards_to_remote(
                    ecv, request.destination_backend_name,
                    keep_local=request.keep_local_dat_file,
                    owner=self.url)
            except (VolumeError, BackendError) as e:
                context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                              str(e))
            yield volume_server_pb2.VolumeTierMoveDatToRemoteResponse(
                processed=total, processed_percentage=100.0)
            return
        total = max(v.content_size, 1)
        progress_state = {"sent": 0}

        def progress(nbytes):
            progress_state["sent"] += nbytes

        try:
            volume_tier.move_dat_to_remote(
                v, request.destination_backend_name,
                keep_local=request.keep_local_dat_file,
                owner=self.url,
                progress=progress)
        except (VolumeError, BackendError) as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        yield volume_server_pb2.VolumeTierMoveDatToRemoteResponse(
            processed=progress_state["sent"],
            processed_percentage=100.0 * progress_state["sent"] / total)

    def VolumeTierMoveDatFromRemote(self, request, context):
        """Download a tiered volume's bulk bytes back to local disk
        (reference volume_grpc_tier_download.go); EC vids restore this
        server's shard files (the COLD -> WARM leg)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            ecv = self.store.find_ec_volume(request.volume_id)
            if ecv is None:
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"volume {request.volume_id} not found")
            try:
                total = volume_tier.move_ec_shards_from_remote(
                    ecv, keep_remote=request.keep_remote_dat_file)
            except (VolumeError, BackendError) as e:
                context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                              str(e))
            yield volume_server_pb2.VolumeTierMoveDatFromRemoteResponse(
                processed=total, processed_percentage=100.0)
            return
        state = {"done": 0}

        def progress(nbytes):
            state["done"] += nbytes

        try:
            total = volume_tier.move_dat_from_remote(
                v, keep_remote=request.keep_remote_dat_file,
                progress=progress)
        except (VolumeError, BackendError) as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        yield volume_server_pb2.VolumeTierMoveDatFromRemoteResponse(
            processed=total, processed_percentage=100.0)

    # -- gRPC: erasure coding --------------------------------------------------

    def VolumeEcShardsGenerate(self, request, context):
        vids = list(request.volume_ids) or [request.volume_id]
        try:
            if len(vids) == 1:
                store_ec.generate_ec_shards(
                    self.store, vids[0],
                    backend=request.encoder or self.ec_encoder)
            else:
                # cross-volume fused encode: one scheduler packs all
                # the volumes' chunks into shared RS dispatches — the
                # pod-scale mesh scheduler under -ec.mesh, the host
                # fleet otherwise
                store_ec.generate_ec_shards_batch(
                    self.store, vids,
                    backend=request.encoder or self.ec_encoder,
                    mesh_cfg=self.ec_mesh_cfg)
        except NeedleError as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        for vid in vids:
            # tier conversion resets the vid's heat ledger: the EC era
            # starts counting from zero (reads re-register on demand)
            self._forget_heat(vid)
        return volume_server_pb2.VolumeEcShardsGenerateResponse()

    def VolumeEcShardsRebuild(self, request, context):
        if request.condemned_shard_ids:
            # a scrub verifier's: rebuild shards held here in place
            try:
                sids = self.scrub.repair_held(
                    request.volume_id, list(request.condemned_shard_ids))
            except KeyError as e:
                context.abort(grpc.StatusCode.NOT_FOUND, str(e))
            except (ValueError, OSError) as e:
                context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
            return volume_server_pb2.VolumeEcShardsRebuildResponse(
                rebuilt_shard_ids=sids)
        # every listed volume (an old client's volume_id alone: a list
        # of one) in one pass of the fleet scheduler, fused by
        # (present, missing) signature
        vids = list(request.volume_ids) or [request.volume_id]
        try:
            rebuilt = store_ec.rebuild_ec_shards_batch(
                self.store, vids, collection=request.collection or None,
                backend=request.encoder or self.ec_encoder)
        except EcShardNotFound as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        for vid, sids in rebuilt.items():
            if sids:
                # rebuilt shard bytes supersede any reconstructed spans
                self._invalidate_volume_cache(vid, "rebuild")
        return volume_server_pb2.VolumeEcShardsRebuildResponse(
            rebuilt_shard_ids=rebuilt[vids[0]],
            results=[volume_server_pb2.VolumeEcShardsRebuildResult(
                volume_id=vid, rebuilt_shard_ids=sids)
                for vid, sids in rebuilt.items()])

    def VolumeEcShardsCopy(self, request, context):
        vid = request.volume_id
        src = volume_stub(request.source_data_node)
        loc = next((l for l in self.store.locations if l.has_free_slot()),
                   self.store.locations[0])
        base = store_ec._base_name(loc.directory, request.collection, vid)
        for sid in request.shard_ids:
            self._pull_file(src, vid, f".ec{sid:02d}",
                            shard_file_name(base, sid),
                            collection=request.collection, is_ec=True)
        if request.copy_ecx_file:
            self._pull_file(src, vid, ".ecx", base + ".ecx",
                            collection=request.collection, is_ec=True)
        if request.copy_ecj_file:
            self._pull_file(src, vid, ".ecj", base + ".ecj",
                            collection=request.collection, is_ec=True,
                            ignore_missing=True)
        return volume_server_pb2.VolumeEcShardsCopyResponse()

    def VolumeEcShardsDelete(self, request, context):
        store_ec.delete_ec_shards(self.store, request.volume_id,
                                  collection=request.collection or None,
                                  shard_ids=list(request.shard_ids))
        # the shard set changed under any cached reconstructed spans
        self._invalidate_volume_cache(request.volume_id, "rebuild")
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeEcShardsDeleteResponse()

    def VolumeEcShardsMount(self, request, context):
        try:
            store_ec.mount_ec_shards(self.store, request.volume_id,
                                     request.collection,
                                     list(request.shard_ids))
        except EcShardNotFound as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeEcShardsMountResponse()

    def VolumeEcShardsUnmount(self, request, context):
        store_ec.unmount_ec_shards(self.store, request.volume_id,
                                   list(request.shard_ids))
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeEcShardsUnmountResponse()

    def VolumeEcShardRead(self, request, context):
        try:
            data = store_ec.read_ec_shard(
                self.store, request.volume_id, request.shard_id,
                request.offset, request.size)
        except EcShardNotFound as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        for i in range(0, len(data), COPY_CHUNK):
            yield volume_server_pb2.VolumeEcShardReadResponse(
                data=data[i:i + COPY_CHUNK])

    def VolumeEcBlobDelete(self, request, context):
        try:
            store_ec.delete_ec_needle(
                self.store, request.volume_id,
                Needle(id=request.file_key), cache=self.read_cache)
        except EcShardNotFound as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        return volume_server_pb2.VolumeEcBlobDeleteResponse()

    def VolumeEcShardsToVolume(self, request, context):
        try:
            store_ec.ec_shards_to_volume(self.store, request.volume_id,
                                         request.collection,
                                         backend=self.ec_encoder)
        except EcShardNotFound as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        # the vid serves from a normal volume now: EC-era cache entries
        # must not outlive the transition (writes can land again), and
        # the EC era's heat ledger resets with the tier
        self._invalidate_volume_cache(request.volume_id, "rebuild")
        self._forget_heat(request.volume_id)
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeEcShardsToVolumeResponse()

    # -- gRPC: scrub control plane ---------------------------------------------

    def VolumeScrubStart(self, request, context):
        # read before the start: the pass it begins ends after this count
        ended = self.scrub.passes_ended
        started = self.scrub.start(
            volume_ids=list(request.volume_ids) or None,
            throttle_mbps=request.throttle_mbps or None,
            full=request.full, alone=request.alone)
        return volume_server_pb2.VolumeScrubStartResponse(
            started=started, passes_ended=ended)

    def VolumeScrubPause(self, request, context):
        return volume_server_pb2.VolumeScrubPauseResponse(
            paused=self.scrub.pause())

    def VolumeScrubStatus(self, request, context):
        if request.wait:
            # in slices: a caller that went away frees this thread
            while context.is_active() and not self.scrub.wait_pass(
                    request.after_passes_ended, timeout=1.0):
                pass
        resp = volume_server_pb2.VolumeScrubStatusResponse(
            **self.scrub.status())
        last = self.scrub.last_pass
        if last is not None:
            resp.last_pass.failed = last.failed
            resp.last_pass.error = last.error
            resp.last_pass.seconds = last.seconds
            for vid, v in sorted(last.verdicts.items()):
                resp.last_pass.volumes.add(
                    volume_id=vid, rebuilt_shard_ids=v.rebuilt_shards,
                    needles_repaired=v.needles_repaired,
                    unrecoverable=v.unrecoverable)
        return resp

    def _fetch_needle_from_replica(self, vid: int, corrupt: Needle):
        """Scrub repair source: the raw stored payload of one needle
        from any OTHER replica. Accept-Encoding gzip keeps a
        compressed needle's stored bytes as stored; cm=false stops the
        replica from resolving a chunk manifest into its chunks. The
        planner validates whatever comes back against the local
        record's own stored CRC, so a stale or corrupt replica copy is
        rejected, never written."""
        fid = f"{vid},{corrupt.id:x}{corrupt.cookie:08x}"
        for url in _breaker.sort_candidates(self._other_replicas(vid)):
            try:
                resp = http_client.request(
                    "GET", f"{url}/{fid}?cm=false",
                    headers={"Accept-Encoding": "gzip"}, timeout=30)
            except OSError:
                continue
            if resp.status == 200:
                return resp.body
        return None

    # -- gRPC: status ----------------------------------------------------------

    def VolumeServerStatus(self, request, context):
        disks = []
        for loc in self.store.locations:
            st = os.statvfs(loc.directory)
            disks.append(volume_server_pb2.DiskStatus(
                dir=loc.directory, all=st.f_blocks * st.f_frsize,
                free=st.f_bavail * st.f_frsize,
                used=(st.f_blocks - st.f_bfree) * st.f_frsize))
        return volume_server_pb2.VolumeServerStatusResponse(
            disk_statuses=disks)

    def VolumeServerLeave(self, request, context):
        """Graceful drain: stop heartbeats so the master forgets us."""
        self._stopping = True
        self._hb_wake.set()
        if self._hb_call is not None:
            self._hb_call.cancel()
        return volume_server_pb2.VolumeServerLeaveResponse()

    def VolumeStatus(self, request, context):
        """Liveness/readonly probe (reference volume_grpc_admin.go
        VolumeStatus)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        return volume_server_pb2.VolumeStatusResponse(
            is_read_only=v.read_only)

    def VolumeNeedleStatus(self, request, context):
        """One needle's metadata without its data (reference
        volume_grpc_query.go VolumeNeedleStatus): index entry + the
        stored record's mtime/crc."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        nv = v.nm.get(request.needle_id)
        if nv is None or not t.size_is_valid(nv.size):
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"needle {request.needle_id} not found")
        # cookie=0 skips the cookie check — this is an admin probe
        try:
            got = v.read_needle(Needle(id=request.needle_id, cookie=0))
        except NeedleError as e:   # expired / torn / CRC-bad record
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        return volume_server_pb2.VolumeNeedleStatusResponse(
            needle_id=request.needle_id,
            cookie=got.cookie,
            size=nv.size,
            last_modified=got.append_at_ns // 1_000_000_000,
            crc=got.checksum,
            ttl=str(v.ttl))

    def VolumeConfigure(self, request, context):
        """Rewrite a volume's replica placement in its superblock
        (reference server/volume_grpc_admin.go:104)."""
        try:
            found = self.store.configure_volume(request.volume_id,
                                                request.replication)
        except (ValueError, VolumeError) as e:
            return volume_server_pb2.VolumeConfigureResponse(error=str(e))
        if not found:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"volume {request.volume_id} not found")
        self.trigger_heartbeat()
        return volume_server_pb2.VolumeConfigureResponse()

    # -- needle data ops (shared by HTTP and gRPC paths) -----------------------

    def _read_needle(self, vid: int, n: Needle,
                     record_heat: bool = True) -> Needle:
        if self.heat is not None and record_heat:
            # counted at admission, not success: a read of a dead
            # needle still heats the volume (the lifecycle policy cares
            # about demand, not hit rate). record_heat=False when the
            # async span fast path already counted this request's
            # admission and fell back here for the payload.
            self.heat.record(vid, n.id)
        if self.store.has_volume(vid):
            got = self.store.read_needle(vid, n)
        elif self.store.find_ec_volume(vid) is not None:
            got = store_ec.read_ec_needle(
                self.store, vid, n,
                remote_reader=self._make_remote_reader(vid),
                cache=self.read_cache, decoder=self.degraded)
        else:
            raise NeedleError(f"volume {vid} not found")
        if _failpoint._armed:
            # injection site volume.read: delay stalls this server's
            # reads (the chaos harness's slow-shard scenario), error
            # fails them, short/corrupt mangle the served payload
            got.data = _failpoint.mangle(
                "volume.read", got.data, vid=str(vid), server=self.url)
        return got

    def _delete_needle(self, vid: int, n: Needle) -> int:
        if self.store.has_volume(vid):
            size = self.store.delete_needle(vid, n)
            self._invalidate_needle_cache(vid, n.id, "delete")
            return size
        if self.store.find_ec_volume(vid) is not None:
            store_ec.delete_ec_needle(self.store, vid, n,
                                      cache=self.read_cache)
            return 0
        raise NeedleError(f"volume {vid} not found")

    # -- read-cache invalidation ----------------------------------------------

    def _invalidate_needle_cache(self, vid: int, needle_id: int,
                                 reason: str) -> None:
        if self.read_cache is not None:
            self.read_cache.invalidate(vid, needle_id, reason)

    def _invalidate_volume_cache(self, vid: int,
                                 reason: str = "scrub_repair") -> None:
        if self.read_cache is not None:
            self.read_cache.invalidate_volume(vid, reason)

    def _make_remote_reader(self, vid: int):
        def fetch_shard(url: str, shard_id: int, offset: int,
                        length: int, exact: bool) -> bytes:
            # deadline: a hung peer must fail this row, not pin
            # the caller (the decode fleet's dispatcher rides
            # this reader — head-of-line blocking is fatal there)
            chunks = [r.data for r in volume_stub(url)
                      .VolumeEcShardRead(
                          volume_server_pb2.VolumeEcShardReadRequest(
                              volume_id=vid, shard_id=shard_id,
                              offset=offset, size=length),
                          timeout=REMOTE_SHARD_READ_S)]
            data = b"".join(chunks)
            if exact and len(data) != length:
                raise EcShardNotFound(
                    f"vid {vid} shard {shard_id}: short remote read")
            return data

        def remote_reader(shard_id: int, offset: int, length: int,
                          exact: bool = True):
            """`length` bytes of the shard from a holder, or None; with
            `exact` False, fewer where the holder's shard ends."""
            urls = _breaker.sort_candidates(
                [u for u in self._ec_shard_locations(vid).get(shard_id, [])
                 if u != self.url])
            tried = bool(urls)
            if self.hedger is not None and len(urls) > 1:
                # a stalled shard holder hedges to another holder after
                # the tracked p95; first response wins
                try:
                    return self.hedger.fetch(
                        [lambda u=u: fetch_shard(u, shard_id, offset,
                                                 length, exact)
                         for u in urls])
                except _deadline.DeadlineExceeded:
                    # a spent budget is the CLIENT's state, not
                    # evidence against these shard locations — never
                    # fall into the forget-locations arm below
                    raise
                except (grpc.RpcError, OSError, EcShardNotFound):
                    pass
            else:
                for url in urls:
                    try:
                        return fetch_shard(url, shard_id, offset, length,
                                           exact)
                    except (grpc.RpcError, EcShardNotFound):
                        continue
            if tried:
                # every known location failed: forget THIS shard's
                # locations so reads stop redialing a dead node
                # (reference forgetShardId, store_ec.go:214-219).
                # Subsequent reads of the shard go straight to
                # reconstruction; the master is re-asked once the
                # view's refresh window lapses (7m at >=10 known
                # shards, 11s once fewer than 10 remain) — the same
                # trade the reference makes
                self._forget_ec_shard(vid, shard_id)
            return None
        return remote_reader

    def _ec_shard_locations(self, vid: int, fresh: bool = False
                            ) -> Dict[int, List[str]]:
        """{shard id: holder urls} of an EC volume, from the master,
        cached as the reference does; `fresh`: ask the master now."""
        now = time.monotonic()
        cached = self._ec_locations.get(vid)
        if cached is not None and not fresh:
            ts, locs = cached
            n_known = len(locs)
            if n_known >= TOTAL_SHARDS:
                window = EC_REFRESH_FULL_S
            elif n_known >= DATA_SHARDS:
                window = EC_REFRESH_PARTIAL_S
            else:
                window = EC_REFRESH_SPARSE_S
            if now - ts < window:
                return locs
        locs = dict(cached[1]) if cached is not None else {}
        try:
            resp = master_stub(self.current_master).LookupEcVolume(
                master_pb2.LookupEcVolumeRequest(volume_id=vid))
            # merge per shard like the reference (store_ec.go:249-257):
            # shards absent from the answer keep their last-known urls
            for sl in resp.shard_id_locations:
                locs[sl.shard_id] = [l.url for l in sl.locations]
        except grpc.RpcError:
            # master unreachable: serve stale cache if any, and don't
            # poison the cache with an empty map until the next window
            return cached[1] if cached is not None else {}
        self._ec_locations[vid] = (now, locs)
        return locs

    def _forget_ec_shard(self, vid: int, shard_id: int) -> None:
        cached = self._ec_locations.get(vid)
        if cached is not None:
            cached[1].pop(shard_id, None)

    def _forget_ec_locations(self, vid: int) -> None:
        self._ec_locations.pop(vid, None)

    # -- replication -----------------------------------------------------------

    def _other_replicas(self, vid: int) -> List[str]:
        """Replica urls for vid, cached per REPLICA_REFRESH_S — the
        pre-cache shape asked the master on EVERY replicated write."""
        now = time.monotonic()
        cached = self._replica_urls.get(vid)
        if cached is not None and now - cached[0] < REPLICA_REFRESH_S:
            return cached[1]
        try:
            resp = master_stub(self.current_master).LookupVolume(
                master_pb2.LookupVolumeRequest(volume_ids=[str(vid)]))
        except grpc.RpcError:
            # master unreachable: serve stale locations if any — a
            # replica POST to a moved node fails and forgets the vid
            return cached[1] if cached is not None else []
        urls = []
        for vl in resp.volume_id_locations:
            for loc in vl.locations:
                if loc.url != self.url:
                    urls.append(loc.url)
        if not urls:
            # never CACHE an empty view: a replica mid-restart is
            # missing from the master for a beat, and banking that
            # would ack 30s of unreplicated writes instead of one
            self._replica_urls.pop(vid, None)
            return urls
        self._replica_urls[vid] = (now, urls)
        return urls

    def _forget_replicas(self, vid: int) -> None:
        self._replica_urls.pop(vid, None)

    def _fan_out_replicas(self, vid: int, urls: List[str], op: str,
                          post_one) -> None:
        """Issue `post_one(url)` for every replica concurrently on the
        shared pool (reference topology/store_replicate.go fans these
        out with goroutines). Every POST runs to completion — an early
        failure never leaves a sibling's in-flight socket dangling to
        poison the keep-alive pool — then the FIRST error fails the
        write and forgets the vid's cached locations.

        Open-breaker peers sort last and their POSTs fail fast inside
        http_client (BreakerOpen) instead of tying a pool lane up for
        a connect timeout — the write still fails (replication is not
        optional) but in microseconds, not seconds."""
        urls = _breaker.sort_candidates(urls)
        from seaweedfs_tpu.stats import trace
        from seaweedfs_tpu.stats.metrics import \
            IngestReplicaFanoutSecondsHistogram
        sp = trace.span("ingest.replicate", vid=vid, op=op,
                        replicas=len(urls)) \
            if trace.is_enabled() else trace.NOOP
        t0 = time.perf_counter()
        with sp:
            outcomes = self._replicate_pool.run(
                [lambda u=u: post_one(u) for u in urls])
        IngestReplicaFanoutSecondsHistogram.labels(op).observe(
            time.perf_counter() - t0)
        first_err = None
        for url, (resp, exc) in zip(urls, outcomes):
            if exc is not None:
                err = f"{op} to {url} failed: {exc}"
            elif resp.status >= 300:
                err = f"{op} to {url} failed: {resp.status}"
            else:
                continue
            if first_err is None:
                first_err = err
        if first_err is not None:
            self._forget_replicas(vid)
            raise NeedleError(first_err)

    def replicated_write(self, vid: int, n: Needle,
                         fsync: bool = False) -> int:
        """Write locally then fan out the serialized needle to every
        other replica CONCURRENTLY (reference
        topology/store_replicate.go:21-94 + its goroutine fan-out).

        Like the reference, a volume whose replica placement says one
        copy never consults the master for replica locations — the
        placement is in the superblock, so the common 000 case stays a
        purely local append."""
        v = self.store.find_volume(vid)
        if v is not None and v.read_only:
            raise NeedleError(f"volume {vid} is read only")
        _, size = self.store.write_needle(vid, n, fsync=fsync)
        self._invalidate_needle_cache(vid, n.id, "overwrite")
        if v is not None and v.replica_placement.copy_count <= 1:
            return size
        urls = self._other_replicas(vid)
        if not urls:
            return size
        blob = n.to_bytes()

        def post_one(url):
            return http_client.request(
                "POST", f"{url}/admin/replicate?volume={vid}",
                body=blob,
                headers={"Content-Type": "application/octet-stream"},
                timeout=30)

        self._fan_out_replicas(vid, urls, "replicate", post_one)
        return size

    def replicated_delete(self, vid: int, n: Needle) -> int:
        size = self._delete_needle(vid, n)
        v = self.store.find_volume(vid)
        if v is not None and v.replica_placement.copy_count <= 1:
            return size
        urls = self._other_replicas(vid)
        if not urls:
            return size

        def post_one(url):
            return http_client.request(
                "POST",
                f"{url}/admin/replicate_delete"
                f"?volume={vid}&key={n.id:x}&cookie={n.cookie:08x}",
                timeout=30)

        self._fan_out_replicas(vid, urls, "replicate_delete", post_one)
        return size


# -- HTTP layer ---------------------------------------------------------------


class _ScrubPeers:
    """What the scrub daemon sees of the other volume servers, through
    this one (scrub/daemon.ScrubDaemon `peers`)."""

    def __init__(self, vs: VolumeServer):
        self._vs = vs

    @property
    def url(self) -> str:
        return self._vs.url

    def locations(self, vid: int) -> Dict[int, List[str]]:
        # asked afresh: every holder applies the verifier rule to the
        # master's present view
        return self._vs._ec_shard_locations(vid, fresh=True)

    def alive(self, url: str) -> bool:
        try:
            volume_stub(url).VolumeServerStatus(
                volume_server_pb2.VolumeServerStatusRequest(),
                timeout=SCRUB_PROBE_S)
        except grpc.RpcError:
            return False
        return True

    def fill(self, vid: int, sid: int, offset: int, view: memoryview) -> int:
        """Shard `sid`'s bytes from `offset` on into `view`, from a
        holder; the count, short only where the holder's shard ends."""
        data = self.reader(vid)(sid, offset, len(view), exact=False)
        if data is None:
            raise ConnectionError(
                f"vid {vid} shard {sid}: no holder gave the bytes")
        view[:len(data)] = data
        return len(data)

    def reader(self, vid: int):
        return self._vs._make_remote_reader(vid)

    def repair(self, url: str, vid: int, sids: List[int]) -> None:
        try:
            volume_stub(url).VolumeEcShardsRebuild(
                volume_server_pb2.VolumeEcShardsRebuildRequest(
                    volume_id=vid, condemned_shard_ids=sids),
                timeout=SCRUB_REPAIR_S)
        except grpc.RpcError as e:
            raise OSError(f"{url}: rebuild of volume {vid} shards "
                          f"{sids}: {e}") from e


def parse_byte_range(rng: str, total: int) -> Tuple[int, int]:
    """Parse a single "bytes=a-b" / "bytes=a-" / "bytes=-n" header
    against a payload of `total` bytes. Returns (start, end) inclusive;
    raises ValueError on anything unsatisfiable (HTTP 416)."""
    start_s, _, end_s = rng[len("bytes="):].partition("-")
    if not start_s:  # suffix range: last N bytes
        start = max(0, total - int(end_s))
        end = total - 1
    else:
        start = int(start_s)
        end = int(end_s) if end_s else total - 1
    end = min(end, total - 1)
    if start > end or start < 0:
        raise ValueError(f"unsatisfiable range {rng!r} for {total}")
    return start, end


def content_disposition(name: str) -> str:
    """inline; filename=... with CR/LF/quotes stripped — names can come
    from attacker-controlled manifest JSON, and a raw CRLF here would
    split the response into injected headers."""
    safe = name.replace("\r", "").replace("\n", "").replace('"', "")
    return f'inline; filename="{safe}"'


def parse_multipart(content_type: str, body: bytes):
    """Returns (filename, mime, data, encoding) of the first file part,
    where encoding is the part's Content-Encoding (reference
    needle_parse_upload.go). Parsing rides util.multipart.iter_parts."""
    from seaweedfs_tpu.util.multipart import iter_parts
    fallback = None
    for _name, filename, headers, data in iter_parts(content_type, body):
        mime = headers.get("content-type", "")
        encoding = headers.get("content-encoding", "")
        if filename:
            return filename, mime, data, encoding
        if fallback is None:
            fallback = ("", mime, data, encoding)
    if fallback is None:
        raise ValueError("empty multipart body")
    return fallback


def _make_http_handler(vs: VolumeServer):
    from seaweedfs_tpu.stats.metrics import instrument_http_handler

    class Handler(FastHandler):
        protocol_version = "HTTP/1.1"
        disable_nagle_algorithm = True  # small replies must not wait on delayed ACKs

        def log_message(self, fmt, *args):
            pass


        # -- plumbing ---------------------------------------------------------

        def _reply(self, code: int, body: bytes = b"",
                   headers: Optional[dict] = None) -> None:
            self.fast_reply(code, body, headers)

        def _json(self, payload: dict, code: int = 200) -> None:
            self.fast_reply(code, json.dumps(payload).encode(),
                            ctype="application/json")

        def _body(self) -> bytes:
            # framing-aware (Content-Length or chunked), identical on
            # both server models
            return self.read_body()

        def _parse_path(self):
            """/<vid>,<key_hex><cookie_hex> with optional leading dirs.

            Manual "?" split instead of urlparse: the data plane never
            carries params/fragments, and urlparse + parse_qs on every
            GET is measurable at small-file rates."""
            path, sep, query = self.path.partition("?")
            return parse_fid(path.lstrip("/")), \
                (parse_qs(query) if sep else {})

        # -- read -------------------------------------------------------------

        def do_GET(self):
            upath = self.path.partition("?")[0]
            if upath == "/status":
                self._json(self.server_status())
                return
            if upath == "/qos/status":
                # the data plane's own QoS admission state (the master
                # aggregates these under /cluster/qos)
                from seaweedfs_tpu import qos
                mgr = qos.manager()
                self._json(mgr.status() if mgr is not None
                           else {"enabled": False})
                return
            if upath in ("/debug/trace", "/debug/requests"):
                # cluster-trace collector + flight recorder on the data
                # port too: cluster.trace fans out over topology node
                # urls, which are HTTP ports, not metrics ports
                from seaweedfs_tpu.stats import cluster_trace
                self._json(cluster_trace.debug_payload(
                    self.path, "volumeServer", vs.url))
                return
            if upath in ("/ui", "/ui/"):
                import html as _html
                st = self.server_status()
                rows = "".join(
                    f"<tr><td>{v['id']}</td>"
                    f"<td>{_html.escape(v.get('collection') or '')}"
                    f"</td><td>{v['size']}</td><td>{v['file_count']}</td>"
                    f"<td>{'ro' if v.get('read_only') else 'rw'}</td></tr>"
                    for v in st["Volumes"])
                body = ("<html><head><title>seaweedfs-tpu volume</title>"
                        f"</head><body><h1>Volume server {vs.url}</h1>"
                        f"<p>master: {vs.current_master}</p>"
                        "<table border=1 cellpadding=4><tr><th>vid</th>"
                        "<th>collection</th><th>size</th><th>files</th>"
                        "<th>mode</th></tr>" + rows + "</table>"
                        "</body></html>").encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/html; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            try:
                f, params = self._parse_path()
            except ValueError as e:
                self._json({"error": str(e)}, code=404)
                return
            n = Needle(id=f.key, cookie=f.cookie)
            if not vs.store.has_volume(f.volume_id) and \
                    vs.store.find_ec_volume(f.volume_id) is None:
                self._redirect_to_replica(f)
                return
            record_heat = True
            if self.async_conn is not None and vs.serve.sendfile and \
                    not _failpoint._armed and \
                    vs.store.has_volume(f.volume_id):
                # zero-copy fast path: payload rides os.sendfile from
                # the volume fd straight to the socket. Falls back to
                # the byte path whenever the payload itself is needed
                # (compressed, chunk manifest, image resize, armed
                # failpoints, strict read verification).
                handled, heat_counted = \
                    self._try_send_needle_span(f, params)
                if handled:
                    return
                record_heat = not heat_counted
            try:
                got = vs._read_needle(f.volume_id, n,
                                      record_heat=record_heat)
                # a local read that outlived the client's budget (slow
                # disk, injected stall) must not get a reply the client
                # stopped waiting for — 504 via the arm below
                _deadline.check(f"volume {f.volume_id} read")
            except CookieMismatch:
                self._reply(404)
                return
            except _deadline.DeadlineExceeded as e:
                # the client's budget ran out somewhere down the read
                # chain (remote shard hop, decode wait): 504, not 404 —
                # the blob may well exist
                self._json({"error": str(e)}, code=504)
                return
            except _failpoint.FailpointError as e:
                # injected read failure: surfaces like the IO error it
                # stands in for
                self._json({"error": str(e)}, code=500)
                return
            except DataCorruptionError as e:
                # corrupt is not missing: a 404 would tell the client
                # the blob never existed; 500 + the scrub counter flags
                # it for repair instead
                from seaweedfs_tpu.stats.metrics import \
                    ScrubCorruptionsFoundCounter
                ScrubCorruptionsFoundCounter.labels("read").inc()
                self._json({"error": str(e)}, code=500)
                return
            except (NeedleError, EcShardNotFound) as e:
                self._json({"error": str(e)}, code=404)
                return
            if got.is_chunk_manifest and \
                    params.get("cm", [""])[0] != "false" and \
                    self._send_chunked(got):
                return
            self._send_needle(got, params)

        do_HEAD = do_GET

        def server_status(self) -> dict:
            return {
                "Version": "seaweedfs-tpu",
                "Volumes": [Store.volume_info(v)
                            for loc in vs.store.locations
                            for v in loc.volumes.values()],
                "Scrub": vs.scrub.status(),
                "Cache": vs.read_cache.stats()
                if vs.read_cache is not None else {"enabled": False},
                "Heat": vs.heat.snapshot()
                if vs.heat is not None else {"enabled": False},
            }

        def _redirect_to_replica(self, f) -> None:
            try:
                resp = master_stub(vs.current_master).LookupVolume(
                    master_pb2.LookupVolumeRequest(
                        volume_ids=[str(f.volume_id)]))
            except grpc.RpcError:
                self._json({"error": "master unreachable"}, code=500)
                return
            candidates = [loc for vl in resp.volume_id_locations
                          for loc in vl.locations if loc.url != vs.url]
            if candidates:
                # never redirect a client INTO a peer this server
                # knows is dead when a healthier replica exists
                loc = min(candidates,
                          key=lambda l: 1 if _breaker.is_open(l.url)
                          else 0)
                self._reply(302, headers={
                    "Location": f"http://{loc.public_url or loc.url}"
                                f"/{f}"})
                return
            self._json({"error": f"volume {f.volume_id} not found"},
                       code=404)

        def _send_chunked(self, got: Needle) -> bool:
            """Resolve a chunk-manifest needle and stream its sub-chunks
            (reference volume_server_handlers_read.go:180-216
            tryHandleChunkedFile). Returns False on a manifest that
            fails to parse, falling back to raw-needle semantics."""
            from seaweedfs_tpu.operation.chunked_file import (
                ChunkedFileReader, load_chunk_manifest)
            try:
                cm = load_chunk_manifest(got.data, got.is_compressed)
            except (ValueError, KeyError, TypeError):
                log.warning("volume %s: unparseable chunk manifest",
                            self.path)
                return False
            reader = ChunkedFileReader(cm.chunks, vs.current_master)
            total = reader.total_size
            headers = {"X-File-Store": "chunked",
                       "Accept-Ranges": "bytes"}
            name = cm.name or (got.name.decode("utf-8", "replace")
                               if got.name else "")
            if name:
                headers["Content-Disposition"] = content_disposition(name)
            if cm.mime and not cm.mime.startswith(
                    "application/octet-stream"):
                headers["Content-Type"] = cm.mime
            status, start, length = 200, 0, total
            rng = self.headers.get("range")
            if rng and rng.startswith("bytes="):
                try:
                    start, end = parse_byte_range(rng, total)
                except ValueError:
                    # RFC 7233 §4.4: 416 carries the representation size
                    self._reply(416, headers={
                        "Content-Range": f"bytes */{total}"})
                    return True
                status = 206
                length = end - start + 1
                headers["Content-Range"] = f"bytes {start}-{end}/{total}"
            self.send_response(status)
            for k, v in headers.items():
                self.send_header(k, v)
            self.send_header("Content-Length", str(length))
            self.end_headers()
            if self.command == "HEAD":
                return True
            sent = 0
            try:
                for block in reader.stream(start, length):
                    self.wfile.write(block)
                    sent += len(block)
            except (RuntimeError, OSError) as e:
                # headers are gone; all we can do is drop the connection
                # so the client sees a short body, like the reference's
                # logged write error
                log.warning("chunked read %s failed after %d bytes: %s",
                            self.path, sent, e)
                self.close_connection = True
            return True

        def _try_send_needle_span(self, f, params) -> tuple:
            """Async zero-copy GET: resolve the needle's payload span
            and reply through send_span (os.sendfile on the async
            connection). Returns (handled, heat_counted): handled
            means a response went out; otherwise the caller falls
            back to the byte path, skipping the heat record iff this
            attempt already counted the admission. Every reply here
            mirrors _send_needle/do_GET byte-for-byte."""
            if vs.heat is not None:
                # admission, exactly where _read_needle counts it
                vs.heat.record(f.volume_id, f.key)
            n = Needle(id=f.key, cookie=f.cookie)
            try:
                got_span = vs.store.read_needle_span(f.volume_id, n)
            except CookieMismatch:
                self._reply(404)
                return True, True
            except NeedleError as e:
                self._json({"error": str(e)}, code=404)
                return True, True
            if got_span is None:
                return False, True
            got, span = got_span
            try:
                _deadline.check(f"volume {f.volume_id} read")
            except _deadline.DeadlineExceeded as e:
                span.close()
                self._json({"error": str(e)}, code=504)
                return True, True
            params = params or {}
            mime = got.mime.decode("utf-8", "replace") if got.mime \
                else ""
            if got.is_compressed or \
                    (got.is_chunk_manifest and
                     params.get("cm", [""])[0] != "false") or \
                    (mime.startswith("image/") and
                     ("width" in params or "height" in params)):
                # the payload itself is needed: byte path owns these
                span.close()
                return False, True
            etag = f'"{got.etag}"'
            if self.headers.get("if-none-match") == etag:
                span.close()
                self._reply(304)
                return True, True
            headers = {"ETag": etag, "Accept-Ranges": "bytes"}
            if got.name:
                headers["Content-Disposition"] = content_disposition(
                    got.name.decode("utf-8", "replace"))
            if mime:
                headers["Content-Type"] = mime
            rng = self.headers.get("range")
            if rng and rng.startswith("bytes="):
                try:
                    start, end = parse_byte_range(rng, span.length)
                except ValueError:
                    span.close()
                    # RFC 7233 §4.4: 416 carries the representation size
                    self._reply(416, headers={
                        "Content-Range": f"bytes */{span.length}"})
                    return True, True
                headers["Content-Range"] = \
                    f"bytes {start}-{end}/{span.length}"
                span.offset += start
                span.length = end - start + 1
                self.send_span(206, span, headers)
                return True, True
            self.send_span(200, span, headers)
            return True, True

        def _send_needle(self, got: Needle,
                         params: Optional[dict] = None) -> None:
            etag = f'"{got.etag}"'
            if self.headers.get("if-none-match") == etag:
                self._reply(304)
                return
            data = got.data
            headers = {"ETag": etag, "Accept-Ranges": "bytes"}
            if got.name:
                headers["Content-Disposition"] = content_disposition(
                    got.name.decode("utf-8", "replace"))
            mime = got.mime.decode("utf-8", "replace") if got.mime else ""
            if mime:
                headers["Content-Type"] = mime
            params = params or {}
            want_resize = mime.startswith("image/") and \
                ("width" in params or "height" in params)
            if got.is_compressed:
                if not want_resize and "gzip" in (
                        self.headers.get("accept-encoding") or ""):
                    headers["Content-Encoding"] = "gzip"
                else:
                    data = gzip.decompress(data)
            if want_resize:
                # EXIF-upright then resize, like the reference read
                # handler (volume_server_handlers_read.go:219-243)
                from seaweedfs_tpu.images import fix_orientation, resized
                data = fix_orientation(data, mime)
                try:
                    width = int(params.get("width", ["0"])[0] or 0)
                    height = int(params.get("height", ["0"])[0] or 0)
                except ValueError:
                    width = height = 0
                data, _, _ = resized(
                    data, mime, width=width, height=height,
                    mode=params.get("mode", [""])[0])
            rng = self.headers.get("range")
            if rng and rng.startswith("bytes=") and not got.is_compressed:
                try:
                    start, end = parse_byte_range(rng, len(data))
                except ValueError:
                    # RFC 7233 §4.4: 416 carries the representation size
                    self._reply(416, headers={
                        "Content-Range": f"bytes */{len(data)}"})
                    return
                headers["Content-Range"] = \
                    f"bytes {start}-{end}/{len(data)}"
                self._reply(206, data[start:end + 1], headers)
                return
            self._reply(200, data, headers)

        # -- write ------------------------------------------------------------

        def do_POST(self):
            upath, sep, query = self.path.partition("?")
            if upath.startswith("/admin/"):
                params = parse_qs(query) if sep else {}
                if upath == "/admin/replicate":
                    self._handle_replicate(params)
                    return
                if upath == "/admin/replicate_delete":
                    self._handle_replicate_delete(params)
                    return
            try:
                f, params = self._parse_path()
            except ValueError as e:
                self._json({"error": str(e)}, code=400)
                return
            body = self._body()
            ctype = self.headers.get("content-type") or ""
            encoding = self.headers.get("content-encoding") or ""
            filename, mime, data = "", ctype, body
            if ctype.startswith("multipart/form-data"):
                try:
                    filename, mime, data, part_enc = \
                        parse_multipart(ctype, body)
                except ValueError as e:
                    self._json({"error": str(e)}, code=400)
                    return
                encoding = part_enc or encoding
            ttl_s = params.get("ttl", [""])[0]
            flags = FLAG_IS_COMPRESSED if encoding.lower() == "gzip" else 0
            if params.get("cm", [""])[0].lower() == "true":
                # chunk-manifest needle (reference
                # needle_parse_upload.go:180: pu.IsChunkedFile)
                flags |= FLAG_IS_CHUNK_MANIFEST
            n = Needle(id=f.key, cookie=f.cookie, data=data,
                       flags=flags,
                       name=filename.encode() if filename else b"",
                       mime=mime.encode() if mime and
                       mime != "application/octet-stream" else b"",
                       ttl=TTL.parse(ttl_s) if ttl_s else None)
            try:
                if params.get("type", [""])[0] == "replicate":
                    _, size = vs.store.write_needle(f.volume_id, n)
                else:
                    size = vs.replicated_write(
                        f.volume_id, n,
                        fsync="fsync" in params)
            except (NeedleError, urllib.error.URLError) as e:
                self._json({"error": str(e)}, code=500)
                return
            self._json({"name": filename, "size": size,
                        "eTag": n.etag}, code=201)

        do_PUT = do_POST

        def _handle_replicate(self, params: dict) -> None:
            vid = int(params["volume"][0])
            try:
                n = Needle.from_bytes(self._body())
                vs.store.write_needle(vid, n)
                vs._invalidate_needle_cache(vid, n.id, "overwrite")
            except NeedleError as e:
                self._json({"error": str(e)}, code=500)
                return
            self._json({"size": n.size}, code=201)

        def _handle_replicate_delete(self, params: dict) -> None:
            vid = int(params["volume"][0])
            n = Needle(id=int(params["key"][0], 16),
                       cookie=int(params["cookie"][0], 16))
            try:
                vs._delete_needle(vid, n)
            except (NeedleError, EcShardNotFound) as e:
                self._json({"error": str(e)}, code=404)
                return
            self._json({}, code=202)

        # -- delete -----------------------------------------------------------

        def do_DELETE(self):
            try:
                f, params = self._parse_path()
            except ValueError as e:
                self._json({"error": str(e)}, code=400)
                return
            n = Needle(id=f.key, cookie=f.cookie)
            try:
                got = vs._read_needle(f.volume_id, n)
                if got.cookie != f.cookie:
                    self._json({"error": "cookie mismatch"}, code=403)
                    return
                chunked_size = None
                if got.is_chunk_manifest:
                    # cascade: all sub-chunks must be gone before the
                    # manifest (reference
                    # volume_server_handlers_write.go:124-137)
                    from seaweedfs_tpu.operation.chunked_file import \
                        load_chunk_manifest
                    try:
                        cm = load_chunk_manifest(got.data,
                                                 got.is_compressed)
                    except (ValueError, KeyError, TypeError) as e:
                        self._json({"error":
                                    f"load chunks manifest: {e}"},
                                   code=500)
                        return
                    try:
                        cm.delete_chunks(vs.current_master)
                    except RuntimeError as e:
                        self._json({"error": f"delete chunks: {e}"},
                                   code=500)
                        return
                    chunked_size = cm.size
                if params.get("type", [""])[0] == "replicate":
                    size = vs._delete_needle(f.volume_id, n)
                else:
                    size = vs.replicated_delete(f.volume_id, n)
                if chunked_size is not None:
                    size = chunked_size
            except CookieMismatch:
                self._json({"error": "cookie mismatch"}, code=403)
                return
            except (NeedleError, EcShardNotFound) as e:
                self._json({"error": str(e)}, code=404)
                return
            self._json({"size": size}, code=202)

    # Prometheus request counter + latency + trace span per HTTP verb
    # (reference volume_server_handlers.go stats wrappers), via the
    # shared role decorator — one instrumentation point for every
    # server role's HTTP plane.
    return instrument_http_handler(Handler, "volumeServer")
