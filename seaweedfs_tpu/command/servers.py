"""Server subcommands: master / volume / filer / s3 / webdav / server.

Flag names and defaults mirror the reference command layer
(weed/command/master.go:29-46, volume.go:65-90, filer.go:43-67,
s3.go:25-35, webdav.go:20-29, server.go) so a ``weed`` user can switch
with the same flags.  Each subcommand blocks until SIGINT/SIGTERM, then
stops its servers via the grace hooks.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
from typing import List, Optional

from seaweedfs_tpu.command import command
from seaweedfs_tpu.util import grace, wlog

log = wlog.logger("command")


def _setup_tls(role: str) -> None:
    """Enable mutual TLS when security.toml carries [grpc.*] sections
    (reference security/tls.go; plaintext without them)."""
    from seaweedfs_tpu.command import setup_client_tls
    setup_client_tls(role)


def _maybe_start_metrics(opts, role: str = "") -> None:
    """Expose Prometheus text metrics on -metricsPort (reference
    stats/metrics.go:172 StartMetricsServer; one shared registry per
    process), plus /healthz (role + uptime) and /debug/trace (Chrome
    trace JSON of the span ring when tracing is enabled)."""
    port = getattr(opts, "metrics_port", 0)
    if port:
        from seaweedfs_tpu.stats.metrics import start_metrics_server
        srv = start_metrics_server(port, role=role)
        grace.on_interrupt(srv.shutdown)
        log.info("metrics exposed on :%d/metrics", port)


def _serve_forever(stoppables: List) -> int:
    done = threading.Event()
    for s in stoppables:
        grace.on_interrupt(s.stop)
    grace.on_interrupt(done.set)
    try:
        while not done.is_set():
            time.sleep(0.5)
    finally:
        grace.run_hooks()
    return 0


def _split_dirs(dir_flag: str) -> List[str]:
    dirs = [d.strip() for d in dir_flag.split(",") if d.strip()]
    for d in dirs:
        os.makedirs(d, exist_ok=True)
    return dirs


def _master_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="master", description="start a master")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=9333)
    p.add_argument("-mdir", default=None,
                   help="data directory for sequence/raft state")
    p.add_argument("-volumeSizeLimitMB", dest="volume_size_limit_mb",
                   type=int, default=30 * 1000)
    p.add_argument("-defaultReplication", dest="default_replication",
                   default="000")
    p.add_argument("-garbageThreshold", dest="garbage_threshold",
                   type=float, default=0.3)
    p.add_argument("-pulseSeconds", dest="pulse_seconds", type=float,
                   default=5.0)
    p.add_argument("-peers", default="",
                   help="comma-separated ip:port of ALL masters "
                        "(including this one) for raft HA")
    p.add_argument("-scrub.intervalSeconds", dest="scrub_interval_s",
                   type=float, default=0.0,
                   help="open one scrub window per volume server every "
                        "N seconds, staggered across the topology "
                        "(0 = disabled)")
    p.add_argument("-scrubMBps", dest="scrub_throttle_mbps", type=float,
                   default=0.0,
                   help="IO budget handed to each scheduled scrub")
    _add_lifecycle_args(p)
    _add_serve_args(p)
    p.add_argument("-cpuprofile", default=None)
    p.add_argument("-metricsPort", dest="metrics_port", type=int,
                   default=0, help="Prometheus /metrics pull port")
    _add_trace_args(p)
    _add_qos_args(p)
    return p


def _add_lifecycle_args(p: argparse.ArgumentParser) -> None:
    """Master-only -lifecycle.* flags (seaweedfs_tpu/lifecycle/): the
    heat-driven policy engine that EC-encodes cold volumes, un-cools
    re-heated ones, and tier-offloads frozen ones. Off by default —
    a master without -lifecycle constructs no engine at all."""
    p.add_argument("-lifecycle", dest="lifecycle", action="store_true",
                   help="enable the heat-driven lifecycle policy "
                        "engine (leader-only; needs volume servers "
                        "running -heat.track)")
    p.add_argument("-lifecycle.dryRun", dest="lifecycle_dry_run",
                   action="store_true",
                   help="log and ledger every decision WITHOUT acting "
                        "— run this first on any real cluster")
    p.add_argument("-lifecycle.intervalSeconds",
                   dest="lifecycle_interval_s", type=float, default=60.0,
                   help="policy pass cadence")
    p.add_argument("-lifecycle.coolThreshold",
                   dest="lifecycle_cool_threshold", type=float,
                   default=0.0,
                   help="window reads at or below this (AND a matching "
                        "EWMA) make a volume a cool-down candidate")
    p.add_argument("-lifecycle.warmThreshold",
                   dest="lifecycle_warm_threshold", type=float,
                   default=50.0,
                   help="window reads at or above this heat a volume "
                        "back up (must exceed coolThreshold — the gap "
                        "is the hysteresis band)")
    p.add_argument("-lifecycle.hotDwellSeconds",
                   dest="lifecycle_hot_dwell_s", type=float,
                   default=600.0,
                   help="minimum residence in HOT before an encode "
                        "(also the write-quiet guard)")
    p.add_argument("-lifecycle.warmDwellSeconds",
                   dest="lifecycle_warm_dwell_s", type=float,
                   default=600.0,
                   help="minimum residence in WARM before any move")
    p.add_argument("-lifecycle.coldDwellSeconds",
                   dest="lifecycle_cold_dwell_s", type=float,
                   default=3600.0,
                   help="minimum residence in COLD before a download")
    p.add_argument("-lifecycle.freezeSeconds",
                   dest="lifecycle_freeze_s", type=float, default=0.0,
                   help="WARM volumes idle this long offload to the "
                        "cold backend (0 = never freeze)")
    p.add_argument("-lifecycle.coldBackend",
                   dest="lifecycle_cold_backend", default="",
                   help="storage backend for the COLD tier, e.g. "
                        "s3.default (empty = COLD disabled)")
    p.add_argument("-lifecycle.maxInflight",
                   dest="lifecycle_max_inflight", type=int, default=2,
                   help="cluster-wide cap on transitions in motion "
                        "per pass")
    p.add_argument("-lifecycle.throttleMBps",
                   dest="lifecycle_throttle_mbps", type=float,
                   default=0.0,
                   help="byte budget pacing transition admission "
                        "(0 = unthrottled)")


def _lifecycle_config(opts):
    if not getattr(opts, "lifecycle", False):
        return None
    from seaweedfs_tpu.lifecycle import LifecycleConfig
    return LifecycleConfig(
        dry_run=opts.lifecycle_dry_run,
        interval_s=opts.lifecycle_interval_s,
        cool_threshold=opts.lifecycle_cool_threshold,
        warm_threshold=opts.lifecycle_warm_threshold,
        hot_dwell_s=opts.lifecycle_hot_dwell_s,
        warm_dwell_s=opts.lifecycle_warm_dwell_s,
        cold_dwell_s=opts.lifecycle_cold_dwell_s,
        freeze_s=opts.lifecycle_freeze_s,
        cold_backend=opts.lifecycle_cold_backend,
        max_inflight=opts.lifecycle_max_inflight,
        throttle_mbps=opts.lifecycle_throttle_mbps)


def _build_master(opts):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.util import config as config_mod
    if opts.mdir:
        os.makedirs(opts.mdir, exist_ok=True)
    peers = [x.strip() for x in (opts.peers or "").split(",") if x.strip()]
    if peers and len(peers) % 2 == 0:
        # the reference enforces an odd master count so elections can't
        # tie (command/master.go:167-196)
        log.warning("master count %d is even; raft needs an odd number "
                    "of peers to avoid split votes", len(peers))
    conf = config_mod.load_configuration("master")
    scripts = conf.get("master.maintenance.scripts") or []
    sleep_minutes = conf.get("master.maintenance.sleep_minutes", 17)
    return MasterServer(
        ip=opts.ip, port=opts.port, meta_dir=opts.mdir,
        volume_size_limit_mb=opts.volume_size_limit_mb,
        default_replication=opts.default_replication,
        pulse_seconds=opts.pulse_seconds,
        garbage_threshold=opts.garbage_threshold,
        peers=peers,
        maintenance_scripts=list(scripts),
        maintenance_interval_s=float(sleep_minutes) * 60,
        scrub_interval_s=opts.scrub_interval_s,
        scrub_throttle_mbps=opts.scrub_throttle_mbps,
        lifecycle=_lifecycle_config(opts),
        sequencer_type=conf.get_string("master.sequencer.type", "memory"),
        sequencer_node_id=conf.get("master.sequencer.node_id"),
        sequencer_etcd_urls=conf.get_string(
            "master.sequencer.sequencer_etcd_urls", "127.0.0.1:2379"),
        serve=_serve_config(opts),
    )


@command("master", "start a master server (control plane)")
def run_master(args) -> int:
    _setup_tls("master")
    opts = _master_parser().parse_args(args)
    _configure_trace(opts)
    _configure_qos(opts)
    grace.setup_profiling(opts.cpuprofile)
    _maybe_start_metrics(opts, role="master")
    m = _build_master(opts)
    m.start()
    return _serve_forever([m])


def _volume_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="volume", description="start a "
                                "volume server")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8080)
    p.add_argument("-dir", default="./data",
                   help="comma-separated storage directories")
    p.add_argument("-max", default="7",
                   help="comma-separated max volume counts per dir")
    p.add_argument("-mserver", default="127.0.0.1:9333")
    p.add_argument("-publicUrl", dest="public_url", default="")
    p.add_argument("-dataCenter", dest="data_center", default="")
    p.add_argument("-rack", default="")
    p.add_argument("-pulseSeconds", dest="pulse_seconds", type=float,
                   default=5.0)
    p.add_argument("-compactionMBps", dest="compaction_mbps", type=float,
                   default=0.0)
    p.add_argument("-scrubMBps", dest="scrub_mbps", type=float,
                   default=0.0,
                   help="IO budget for the background integrity scrub "
                        "(0 = unthrottled)")
    p.add_argument("-scrub.intervalSeconds", dest="scrub_interval_s",
                   type=float, default=0.0,
                   help="re-scrub every N seconds (0 = only on demand "
                        "via volume.scrub / the master scheduler)")
    p.add_argument("-ec.encoder", dest="ec_encoder", default="auto",
                   choices=["auto", "jax", "native", "numpy"])
    p.add_argument("-ec.mesh", dest="ec_mesh", action="store_true",
                   default=False,
                   help="run batched EC encode/verify/decode on the "
                        "unified pod-scale mesh scheduler (one "
                        "scheduler feeding all jax devices; falls "
                        "back per pass to the per-device fleet on "
                        "any mesh failure)")
    p.add_argument("-ec.meshMinVolumes", dest="ec_mesh_min_volumes",
                   type=int, default=0,
                   help="smallest volume batch worth sharding over "
                        "the mesh (0 = the mesh's dp axis size)")
    p.add_argument("-ec.meshBucketMB", dest="ec_mesh_bucket_mb",
                   type=int, default=32,
                   help="data bytes per fused [dp, 10, span] mesh "
                        "bucket upload")
    p.add_argument("-ec.meshTimeoutS", dest="ec_mesh_timeout_s",
                   type=float, default=30.0,
                   help="bucket dispatch stall bound before the pass "
                        "abandons the mesh and falls back (0 = wait "
                        "forever; also capped by the request "
                        "deadline)")
    p.add_argument("-cache.sizeMB", dest="cache_size_mb", type=int,
                   default=0,
                   help="RAM budget for the tiered read cache "
                        "(0 = disabled; serves hot EC/needle reads and "
                        "reconstructed spans)")
    p.add_argument("-cache.dir", dest="cache_dir", default="",
                   help="directory for the read cache's disk tier "
                        "(empty = RAM tier only)")
    p.add_argument("-degraded.fleet", dest="degraded_fleet",
                   type=lambda s: s.lower() not in ("0", "false", "no"),
                   default=True,
                   help="fuse concurrent degraded-read reconstructions "
                        "into batched RS decode dispatches (false = "
                        "per-interval in-place recovery)")
    p.add_argument("-replicate.parallel", dest="replicate_parallel",
                   type=int, default=8,
                   help="replica POSTs issued concurrently per "
                        "replicated write (1 = serial fan-out)")
    p.add_argument("-degraded.batchMs", dest="degraded_batch_ms",
                   type=float, default=2.0,
                   help="decode-fleet batch window in milliseconds: how "
                        "long a reconstruction waits to fuse with "
                        "concurrent ones")
    p.add_argument("-index", dest="needle_map_kind", default="memory",
                   choices=["memory", "kv"],
                   help="needle map kind: memory (dict rebuild from .idx) "
                        "or kv (persistent LogKV, O(live) reopen; reference "
                        "command/volume.go:203-211 leveldb kinds)")
    p.add_argument("-heat.track", dest="heat_track", action="store_true",
                   help="per-volume (and sampled per-needle) read-path "
                        "heat telemetry: SeaweedFS_volume_heat{vid} + "
                        "the Heat block on /status")
    p.add_argument("-heat.windowSeconds", dest="heat_window_s",
                   type=float, default=60.0,
                   help="sliding window the heat gauge counts reads "
                        "over")
    p.add_argument("-cpuprofile", default=None)
    p.add_argument("-metricsPort", dest="metrics_port", type=int,
                   default=0, help="Prometheus /metrics pull port")
    _add_resilience_args(p)
    _add_trace_args(p)
    _add_serve_args(p)
    _add_qos_args(p)
    return p


def _add_serve_args(p: argparse.ArgumentParser) -> None:
    """Shared -serve.* flags (every HTTP role; util/async_server.py).
    Off by default — the threaded model serves and no async machinery
    is ever constructed."""
    p.add_argument("-serve.async", dest="serve_async",
                   action="store_true",
                   help="serve HTTP on the selector event loop (one "
                        "poll loop + a bounded worker pool) instead "
                        "of a thread per connection; responses are "
                        "byte-identical, GET payloads ride zero-copy "
                        "os.sendfile")
    p.add_argument("-serve.maxConns", dest="serve_max_conns",
                   type=int, default=0,
                   help="open-connection cap for -serve.async; past "
                        "it the listener stops accepting until "
                        "connections close (0 = built-in 4096)")
    p.add_argument("-serve.keepAliveBudget",
                   dest="serve_keepalive_budget", type=int, default=0,
                   help="idle keep-alive connections retained by "
                        "-serve.async; past it the least-recently-"
                        "active idle connection is closed (0 = "
                        "built-in 1024)")
    p.add_argument("-serve.workers", dest="serve_workers", type=int,
                   default=0,
                   help="handler worker threads for -serve.async "
                        "(spawned lazily on the first requests; 0 = "
                        "built-in 16)")
    p.add_argument("-serve.sendfile", dest="serve_sendfile",
                   type=lambda s: s.lower() not in ("0", "false", "no"),
                   default=True,
                   help="zero-copy GET payloads via os.sendfile under "
                        "-serve.async (false = copy through userspace; "
                        "payload CRC-on-read semantics like the "
                        "threaded model)")


def _serve_config(opts):
    """ServeConfig from the -serve.* flags; None stays the threaded
    default without importing anything."""
    from seaweedfs_tpu.util.http_server import ServeConfig
    return ServeConfig(
        async_mode=getattr(opts, "serve_async", False),
        max_conns=getattr(opts, "serve_max_conns", 0),
        keepalive_budget=getattr(opts, "serve_keepalive_budget", 0),
        workers=getattr(opts, "serve_workers", 0),
        sendfile=getattr(opts, "serve_sendfile", True))


def _add_trace_args(p: argparse.ArgumentParser) -> None:
    """Shared -trace.* flags (every role; see stats/cluster_trace.py).
    Off by default — the cluster tracer costs one flag check per seam
    until enabled."""
    p.add_argument("-trace.sample", dest="trace_sample", type=float,
                   default=-1.0,
                   help="enable cluster tracing; head-sample this "
                        "fraction of requests unconditionally (0 = "
                        "tail-only: keep slow/errored requests; "
                        "negative = tracing disabled)")
    p.add_argument("-trace.slowMs", dest="trace_slow_ms", type=float,
                   default=200.0,
                   help="floor for the tail-sampling keep threshold: a "
                        "request slower than max(this, the tracked "
                        "per-verb p95) pins its span detail")


def _configure_trace(opts) -> None:
    if getattr(opts, "trace_sample", -1.0) >= 0:
        from seaweedfs_tpu.stats import cluster_trace
        cluster_trace.enable(sample_fraction=opts.trace_sample,
                             slow_threshold_ms=opts.trace_slow_ms)
        log.info("cluster tracing on (sample=%.3f slowMs=%.0f)",
                 cluster_trace.sample, cluster_trace.slow_ms)


def _add_resilience_args(p: argparse.ArgumentParser) -> None:
    """Shared -resilience.* flags (volume + filer; see
    seaweedfs_tpu/resilience/). Everything defaults OFF — the
    resilience layer costs nothing until enabled."""
    p.add_argument("-resilience.breaker", dest="resilience_breaker",
                   action="store_true",
                   help="per-peer circuit breakers: fail fast on dead "
                        "peers instead of waiting out connect timeouts")
    p.add_argument("-resilience.breakerThreshold",
                   dest="resilience_breaker_threshold", type=int,
                   default=5,
                   help="consecutive failures that open a peer's breaker")
    p.add_argument("-resilience.breakerCooldownS",
                   dest="resilience_breaker_cooldown", type=float,
                   default=5.0,
                   help="seconds an open breaker waits before the "
                        "half-open probe")
    p.add_argument("-resilience.hedge", dest="resilience_hedge",
                   action="store_true",
                   help="hedged reads: after the tracked p95, send one "
                        "speculative request to another replica/shard "
                        "holder (<=5%% extra-request budget)")
    p.add_argument("-resilience.hedgeDelayMs",
                   dest="resilience_hedge_delay_ms", type=float,
                   default=10.0,
                   help="floor for the hedge delay (the tracked p95 "
                        "takes over once measured)")


def _configure_resilience(opts) -> None:
    if opts.resilience_breaker:
        from seaweedfs_tpu.resilience import breaker
        breaker.configure(
            enable=True,
            threshold=opts.resilience_breaker_threshold,
            cooldown_s=opts.resilience_breaker_cooldown)


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").lower() in ("1", "true", "yes", "on")


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name, "")
    try:
        return float(v) if v else default
    except ValueError:
        return default


def _add_qos_args(p: argparse.ArgumentParser) -> None:
    """Shared -qos.* flags (master/volume/filer/s3/server; see
    seaweedfs_tpu/qos/). Everything defaults OFF — with QoS disabled no
    bucket exists, no tenant is resolved, and every seam costs one
    identity check (tests/test_perf_gates.py::test_qos_disabled_overhead).
    SEAWEED_QOS* environment variables supply fleet-wide defaults the
    flags override per process."""
    p.add_argument("-qos", dest="qos", action="store_true",
                   default=_env_flag("SEAWEED_QOS"),
                   help="enable multi-tenant QoS: per-tenant admission "
                        "buckets, weighted-fair pool scheduling, and "
                        "explicit 429/503+Retry-After backpressure "
                        "(env default SEAWEED_QOS)")
    p.add_argument("-qos.requestRate", dest="qos_request_rate",
                   type=float,
                   default=_env_float("SEAWEED_QOS_REQUEST_RATE", 0.0),
                   help="per-tenant admitted requests/second (0 = "
                        "unlimited; env default SEAWEED_QOS_REQUEST_RATE)")
    p.add_argument("-qos.requestBurst", dest="qos_request_burst",
                   type=float, default=0.0,
                   help="per-tenant request burst cap (0 = 2x rate)")
    p.add_argument("-qos.bytesMBps", dest="qos_bytes_mbps", type=float,
                   default=_env_float("SEAWEED_QOS_BYTES_MBPS", 0.0),
                   help="per-tenant admitted ingress MB/s judged from "
                        "Content-Length (0 = unlimited; env default "
                        "SEAWEED_QOS_BYTES_MBPS)")
    p.add_argument("-qos.bytesBurstS", dest="qos_bytes_burst_s",
                   type=float, default=2.0,
                   help="seconds of byte budget a tenant may bank")
    p.add_argument("-qos.globalRequestRate", dest="qos_global_rate",
                   type=float, default=0.0,
                   help="whole-process admitted requests/second across "
                        "all tenants; when heat shedding is armed a "
                        "quarter of it is reserved for hot-volume "
                        "traffic so cold reads shed first (0 = "
                        "unlimited)")
    p.add_argument("-qos.weights", dest="qos_weights",
                   default=os.environ.get("SEAWEED_QOS_WEIGHTS", ""),
                   help="per-tenant fair-share weights as "
                        "name:weight,name:weight (env default "
                        "SEAWEED_QOS_WEIGHTS)")
    p.add_argument("-qos.defaultWeight", dest="qos_default_weight",
                   type=float, default=1.0,
                   help="fair-share weight for tenants not in "
                        "-qos.weights")
    p.add_argument("-qos.internalWeight", dest="qos_internal_weight",
                   type=float, default=0.25,
                   help="fair-share weight of the _internal tenant "
                        "(scrub/lifecycle/filer_sync background work)")
    p.add_argument("-qos.maxTenants", dest="qos_max_tenants", type=int,
                   default=64,
                   help="distinct tenants tracked before the overflow "
                        "tenant _other absorbs the rest (bounds bucket "
                        "memory and metric label cardinality)")
    p.add_argument("-qos.heatShed", dest="qos_heat_shed",
                   type=lambda s: s.lower() not in ("0", "false", "no"),
                   default=True,
                   help="under global overload, prefer shedding reads "
                        "of cold volumes (needs -heat.track on the "
                        "volume server; false = shed uniformly)")


def _parse_qos_weights(spec: str) -> dict:
    weights = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        try:
            weights[name.strip()] = float(w)
        except ValueError:
            raise SystemExit(
                f"-qos.weights: expected name:weight, got {part!r}")
    return weights


def _configure_qos(opts) -> None:
    """Build and install the process-wide QosManager from the -qos.*
    flags. Without -qos nothing is imported and every seam stays None
    (the combined `server` role shares the one manager across all its
    roles — there is exactly one per process by design)."""
    if not getattr(opts, "qos", False):
        return
    from seaweedfs_tpu import qos
    from seaweedfs_tpu.qos.admission import QosConfig
    qos.configure(QosConfig(
        request_rate=opts.qos_request_rate,
        request_burst=opts.qos_request_burst,
        bytes_mbps=opts.qos_bytes_mbps,
        bytes_burst_s=opts.qos_bytes_burst_s,
        global_request_rate=opts.qos_global_rate,
        weights=_parse_qos_weights(opts.qos_weights),
        default_weight=opts.qos_default_weight,
        internal_weight=opts.qos_internal_weight,
        max_tenants=opts.qos_max_tenants,
        heat_shed=opts.qos_heat_shed))
    log.info("qos on (rate=%s/s bytes=%sMB/s global=%s/s)",
             opts.qos_request_rate or "inf",
             opts.qos_bytes_mbps or "inf",
             opts.qos_global_rate or "inf")


def _attach_qos_heat(vs) -> None:
    """Hand the volume server's HeatTracker to the QoS manager so
    -qos.heatShed can tell hot volumes from cold under global
    overload. No-op unless BOTH -qos and -heat.track are on."""
    from seaweedfs_tpu import qos
    mgr = qos.manager()
    if mgr is not None and getattr(vs, "heat", None) is not None:
        mgr.heat = vs.heat


def _storage_backend_conf() -> dict:
    """Flatten master.toml's [storage.backend.<scheme>.<id>] sections to
    {"scheme.id": props} (reference backend.go LoadConfiguration)."""
    from seaweedfs_tpu.util import config as config_mod
    conf = config_mod.load_configuration("master")
    tree = conf.get("storage.backend") or {}
    flat = {}
    for scheme, ids in tree.items():
        if not isinstance(ids, dict):
            continue
        for ident, props in ids.items():
            if isinstance(props, dict) and props.get("enabled", True):
                flat[f"{scheme}.{ident}"] = {
                    k: v for k, v in props.items() if k != "enabled"}
    return flat


def _build_volume(opts):
    from seaweedfs_tpu.server.volume import VolumeServer
    dirs = _split_dirs(opts.dir)
    maxes = [int(x) for x in str(opts.max).split(",")]
    if len(maxes) == 1:
        maxes = maxes * len(dirs)
    return VolumeServer(
        opts.mserver, dirs, ip=opts.ip, port=opts.port,
        public_url=opts.public_url, data_center=opts.data_center,
        rack=opts.rack, max_volume_counts=maxes,
        pulse_seconds=opts.pulse_seconds, ec_encoder=opts.ec_encoder,
        compaction_mbps=opts.compaction_mbps,
        storage_backends=_storage_backend_conf(),
        needle_map_kind=opts.needle_map_kind,
        scrub_mbps=opts.scrub_mbps,
        scrub_interval_s=opts.scrub_interval_s,
        cache_size_mb=opts.cache_size_mb,
        cache_dir=opts.cache_dir or None,
        degraded_fleet=opts.degraded_fleet,
        degraded_batch_ms=opts.degraded_batch_ms,
        replicate_parallel=opts.replicate_parallel,
        hedge_reads=opts.resilience_hedge,
        hedge_delay_ms=opts.resilience_hedge_delay_ms,
        heat_track=opts.heat_track,
        heat_window_s=opts.heat_window_s,
        ec_mesh=opts.ec_mesh,
        ec_mesh_min_volumes=opts.ec_mesh_min_volumes,
        ec_mesh_bucket_mb=opts.ec_mesh_bucket_mb,
        ec_mesh_timeout_s=opts.ec_mesh_timeout_s,
        serve=_serve_config(opts))


@command("volume", "start a volume server (data plane)")
def run_volume(args) -> int:
    _setup_tls("volume")
    opts = _volume_parser().parse_args(args)
    _configure_resilience(opts)
    _configure_trace(opts)
    _configure_qos(opts)
    grace.setup_profiling(opts.cpuprofile)
    _maybe_start_metrics(opts, role="volume")
    vs = _build_volume(opts)
    _attach_qos_heat(vs)
    vs.start()
    return _serve_forever([vs])


def _filer_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="filer", description="start a filer")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8888)
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-store", default="sqlite",
                   help="metadata store: memory | sqlite | weedkv "
                        "(embedded log-structured KV) | redis | etcd | "
                        "mysql | postgres (connection params come from "
                        "the matching filer.toml section)")
    p.add_argument("-dir", default="./filer",
                   help="directory for metadata store + event log")
    p.add_argument("-collection", default="")
    p.add_argument("-defaultReplicaPlacement", dest="replication",
                   default="")
    p.add_argument("-maxMB", dest="max_mb", type=int, default=32,
                   help="auto-chunking split size")
    p.add_argument("-encryptVolumeData", dest="cipher",
                   action="store_true")
    p.add_argument("-ingest.parallelism", dest="ingest_parallelism",
                   type=int, default=8,
                   help="chunk uploads in flight per multi-chunk body "
                        "(1 = fully serial ingest, no pool threads)")
    p.add_argument("-assign.leaseCount", dest="assign_lease_count",
                   type=int, default=0,
                   help="lease N fids per master assign and hand them "
                        "out locally (0 = one assign per chunk)")
    p.add_argument("-peers", default="",
                   help="comma-separated host:port of ALL filers in "
                        "this cluster (merged metadata view)")
    p.add_argument("-meta.lookupTTL", dest="meta_lookup_ttl_s",
                   type=float, default=0.0,
                   help="arm the coalescing volume-lookup cache: "
                        "positive answers live this many seconds, "
                        "concurrent misses single-flight, and misses "
                        "within the coalesce window fuse into one "
                        "batched /dir/lookup (0 = off, one gRPC "
                        "round trip per lookup)")
    p.add_argument("-meta.lookupNegativeTTL",
                   dest="meta_lookup_negative_ttl_s", type=float,
                   default=2.0,
                   help="seconds a NOT-FOUND lookup answer is served "
                        "from cache (bounds miss storms on deleted "
                        "volumes; only with -meta.lookupTTL)")
    p.add_argument("-meta.lookupCoalesceMs",
                   dest="meta_lookup_coalesce_ms", type=float,
                   default=2.0,
                   help="how long a lookup miss waits for siblings "
                        "to join its batched master round trip "
                        "(only with -meta.lookupTTL)")
    p.add_argument("-meta.lookupBatchMax",
                   dest="meta_lookup_batch_max", type=int, default=128,
                   help="most vids fused into one batched lookup "
                        "round trip (only with -meta.lookupTTL)")
    p.add_argument("-meta.listingCacheMB",
                   dest="meta_listing_cache_mb", type=int, default=0,
                   help="RAM budget for the directory-listing page "
                        "cache, invalidated by the metadata event "
                        "log (0 = off, every listing walks the "
                        "filer store)")
    p.add_argument("-metricsPort", dest="metrics_port", type=int,
                   default=0, help="Prometheus /metrics pull port")
    _add_resilience_args(p)
    _add_trace_args(p)
    _add_serve_args(p)
    return p


def _configure_meta(opts) -> None:
    """Arm the process-wide coalescing lookup cache from the -meta.*
    flags (wdclient/lookup_cache.py module seam). Off by default: the
    module stays disabled and no call site constructs a cache."""
    ttl = getattr(opts, "meta_lookup_ttl_s", 0.0)
    if ttl and ttl > 0:
        from seaweedfs_tpu.wdclient import lookup_cache
        lookup_cache.configure(
            enable=True, ttl_s=ttl,
            negative_ttl_s=opts.meta_lookup_negative_ttl_s,
            coalesce_ms=opts.meta_lookup_coalesce_ms,
            batch_max=opts.meta_lookup_batch_max)


def _build_filer(opts):
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.util import config as config_mod
    os.makedirs(opts.dir, exist_ok=True)
    peers = [x.strip() for x in (opts.peers or "").split(",")
             if x.strip()]
    # the store's filer.toml section carries its connection params
    # (reference scaffold.go [redis]/[etcd]/[mysql]/[postgres])
    store_options = config_mod.load_configuration("filer") \
        .get(opts.store) or {}
    fs = FilerServer(
        opts.master, ip=opts.ip, port=opts.port, store=opts.store,
        store_options=store_options,
        meta_dir=opts.dir, collection=opts.collection,
        replication=opts.replication,
        chunk_size=opts.max_mb << 20, cipher=opts.cipher,
        cache_dir=os.path.join(opts.dir, "cache"),
        peers=peers,
        ingest_parallelism=opts.ingest_parallelism,
        assign_lease_count=opts.assign_lease_count,
        hedge_reads=opts.resilience_hedge,
        hedge_delay_ms=opts.resilience_hedge_delay_ms,
        listing_cache_mb=getattr(opts, "meta_listing_cache_mb", 0),
        serve=_serve_config(opts))
    # notification.toml: publish every metadata mutation to the first
    # enabled [notification.X] queue (reference filer.go
    # LoadConfiguration("notification"))
    from seaweedfs_tpu import notification
    queue = notification.from_config(
        config_mod.load_configuration("notification"))
    if queue is not None:
        fs.filer.notification_queue = queue
    return fs


@command("filer", "start a filer (namespace server)")
def run_filer(args) -> int:
    _setup_tls("filer")
    opts = _filer_parser().parse_args(args)
    _configure_resilience(opts)
    _configure_trace(opts)
    _configure_qos(opts)
    _configure_meta(opts)   # BEFORE the build: MasterClient arms at init
    _maybe_start_metrics(opts, role="filer")
    fs = _build_filer(opts)
    fs.start()
    return _serve_forever([fs])


def _load_iam(config_path: Optional[str]):
    """IAM identities from an s3.configure-style JSON file:
    {"identities": [{"name":..., "credentials": [{"accessKey":...,
    "secretKey":...}], "actions": ["Read","Write",...]}]}"""
    from seaweedfs_tpu.s3api.auth import Iam, Identity, Credential
    if not config_path:
        return Iam()
    with open(config_path) as f:
        cfg = json.load(f)
    idents = []
    for ident in cfg.get("identities", []):
        creds = [Credential(c["accessKey"], c["secretKey"])
                 for c in ident.get("credentials", [])]
        idents.append(Identity(name=ident.get("name", ""),
                               credentials=creds,
                               actions=ident.get("actions", [])))
    return Iam(idents)


def _s3_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="s3", description="start an S3 "
                                "gateway on a filer")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8333)
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-config", default=None,
                   help="JSON file with IAM identities")
    p.add_argument("-metricsPort", dest="metrics_port", type=int,
                   default=0, help="Prometheus /metrics pull port")
    _add_serve_args(p)
    _add_qos_args(p)
    return p


@command("s3", "start an S3-compatible gateway")
def run_s3(args) -> int:
    opts = _s3_parser().parse_args(args)
    _configure_qos(opts)
    _maybe_start_metrics(opts, role="s3")
    from seaweedfs_tpu.s3api.server import S3ApiServer
    s3 = S3ApiServer(opts.filer, ip=opts.ip, port=opts.port,
                     iam=_load_iam(opts.config),
                     serve=_serve_config(opts))
    s3.start()
    return _serve_forever([s3])


def _webdav_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="webdav", description="start a "
                                "WebDAV gateway on a filer")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=7333)
    p.add_argument("-filer", default="127.0.0.1:8888")
    _add_serve_args(p)
    return p


@command("ftp", "start an FTP gateway over the filer")
def run_ftp(args) -> int:
    _setup_tls("client")
    p = argparse.ArgumentParser(prog="ftp")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=2121)
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-ftpRoot", dest="ftp_root", default="/")
    opts = p.parse_args(args)
    from seaweedfs_tpu.ftpd import FtpServer
    srv = FtpServer(opts.filer, ip=opts.ip, port=opts.port,
                    ftp_root=opts.ftp_root)
    srv.start()
    return _serve_forever([srv])


@command("webdav", "start a WebDAV gateway")
def run_webdav(args) -> int:
    opts = _webdav_parser().parse_args(args)
    from seaweedfs_tpu.server.webdav import WebDavServer
    wd = WebDavServer(opts.filer, ip=opts.ip, port=opts.port,
                      serve=_serve_config(opts))
    wd.start()
    return _serve_forever([wd])


@command("server", "start master + volume (+filer, +s3) in one process")
def run_server(args) -> int:
    p = argparse.ArgumentParser(prog="server", description="combined "
                                "cluster-in-one-process (reference weed "
                                "server)")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-dir", default="./data")
    p.add_argument("-master.port", dest="master_port", type=int,
                   default=9333)
    p.add_argument("-volume.port", dest="volume_port", type=int,
                   default=8080)
    p.add_argument("-volume.max", dest="volume_max", default="7")
    p.add_argument("-filer", action="store_true",
                   help="also start a filer")
    p.add_argument("-filer.port", dest="filer_port", type=int,
                   default=8888)
    p.add_argument("-s3", action="store_true",
                   help="also start an S3 gateway (implies -filer)")
    p.add_argument("-s3.port", dest="s3_port", type=int, default=8333)
    p.add_argument("-volumeSizeLimitMB", dest="volume_size_limit_mb",
                   type=int, default=30 * 1000)
    _add_qos_args(p)
    opts = p.parse_args(args)
    # one process-wide manager shared by every role in the combined
    # server: all of them meter against the same tenant buckets
    _configure_qos(opts)

    mopts = _master_parser().parse_args(
        ["-ip", opts.ip, "-port", str(opts.master_port),
         "-mdir", os.path.join(opts.dir, "master"),
         "-volumeSizeLimitMB", str(opts.volume_size_limit_mb)])
    master = _build_master(mopts)
    master.start()

    vopts = _volume_parser().parse_args(
        ["-ip", opts.ip, "-port", str(opts.volume_port),
         "-dir", os.path.join(opts.dir, "volume"),
         "-max", str(opts.volume_max),
         "-mserver", f"{opts.ip}:{opts.master_port}"])
    vol = _build_volume(vopts)
    _attach_qos_heat(vol)
    vol.start()

    stack = [master, vol]
    if opts.filer or opts.s3:
        fopts = _filer_parser().parse_args(
            ["-ip", opts.ip, "-port", str(opts.filer_port),
             "-master", f"{opts.ip}:{opts.master_port}",
             "-dir", os.path.join(opts.dir, "filer")])
        filer = _build_filer(fopts)
        filer.start()
        stack.append(filer)
        if opts.s3:
            from seaweedfs_tpu.s3api.server import S3ApiServer
            s3 = S3ApiServer(f"{opts.ip}:{opts.filer_port}", ip=opts.ip,
                             port=opts.s3_port)
            s3.start()
            stack.append(s3)
    return _serve_forever(stack)
