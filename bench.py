"""Headline benchmark: EC encode+rebuild throughput, TPU vs CPU baseline.

Measures BOTH halves of the RS(10,4) GF(2^8) north star — encode (the
compute behind `ec.encode`, reference /root/reference
weed/storage/erasure_coding/ec_encoder.go:162-192) and rebuild (the
Cauchy-inverse map behind `ec.rebuild`/RebuildEcFiles,
ec_encoder.go:233-287). Both are the same bit-matmul kernel with
different matrices; the CPU stand-in for each is the C++ AVX2 library
in seaweedfs_tpu/native (klauspost/reedsolomon's role).

On-device timing discipline: one dispatch per timed repetition, with
ITERS encodes chained inside a single jit via lax.fori_loop. Two
properties make the measurement honest (a GF(2^8) linear map is
per-byte-column, so weaker versions let XLA slice the computation):

  1. Sequential data dependence on the FULL parity: iteration i+1's
     input is `data ^ tile(parity_i)` — every output byte of encode i
     feeds encode i+1, so no iteration can be hoisted or elided.
  2. The fetched scalar is a sum over the entire final state, so every
     lane column is live — no dead-column slicing.

The working set (10 x 32MB = 320MB) far exceeds VMEM, so each encode
must stream from HBM, and the reported GB/s is sanity-bounded against
the chip's HBM bandwidth (DEVICE_PEAKS, keyed by the `device_kind` the
device reports): a number above it is a measurement bug by definition
and the bench fails rather than prints. A kind that is not in the table
is an error, never a default.

Timing: the host clock around one dispatch ending in
`block_until_ready()`. Established on the attached chip (PR 22, TPU v5
lite, jax 0.9.0): the enqueue returns in ~0.2 ms, `block_until_ready()`
then waits out the device work (12.4 ms for three chained 8192^3 bf16
matmuls), and a scalar fetch after it costs ~2 ms — so the block IS the
synchronization and the fetch adds nothing but its own latency. The
scalar is still fetched after the clock stops, because its value (a sum
over the entire final state) is what keeps every column live.

The device phases need an accelerator: without one they fail, and every
result line names the platform, `device_kind` and device count it ran
on. ITERS chained encodes per dispatch: value kept from earlier work,
not measured on the attached chip.

Prints ONE json line:
  {"metric": "ec_encode_rebuild_gbps", "value": <TPU GB/s>, "unit": "GB/s",
   "vs_baseline": <ratio vs native CPU single-thread>, ...}
where value is the combined encode-then-rebuild throughput (harmonic
mean of the two phase throughputs: GB processed per second when every
byte is encoded once and rebuilt once), plus per-phase fields.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

DATA_SHARDS = 10
LANES = 32 << 20          # 32MB lanes -> 320MB data per encode
ITERS = 64                # encodes chained per dispatch (not measured
                          # on the attached chip)
REPS = 3                  # timed dispatches; best taken
CPU_LANES = 8 << 20       # 80MB for the CPU baseline measurement


# Published single-chip peaks, keyed by the string the device REPORTS as
# `device_kind` (never a marketing name written from memory: the v5e
# reports "TPU v5 lite"). Each chained encode must stream its 320MB
# working set from HBM (>> VMEM) at least once (read d) and write it
# back (d ^ fold), so encoded-GB/s above the chip's HBM bandwidth is
# physically impossible — a measurement bug, not speed.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM at 819 GB/s,
    # 197 TFLOP/s bf16, 393 TOP/s int8. Reported kind checked on the
    # attached chip (PR 22, jax 0.9.0 / libtpu 0.0.34).
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0,
                    "int8_tops": 393.0},
}


class UnknownDeviceKind(RuntimeError):
    """The device reports a kind DEVICE_PEAKS has no entry for."""


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"device_kind {device_kind!r} is not in bench.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)}); add its published peaks "
            "with their source before benchmarking on it") from None


def require_accelerator() -> dict:
    """The device a device phase will run on, as JAX reports it — or
    SystemExit when JAX found no accelerator (a CPU run must never be
    printed under a device metric's name)."""
    import jax

    from seaweedfs_tpu.util import compile_cache
    compile_cache.configure()
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform == "cpu":
        raise SystemExit(
            f"bench: no accelerator: JAX found platform {d0.platform!r} "
            f"({d0.device_kind!r} x{len(devices)}); the device phases "
            "run on a chip only")
    device_peaks(d0.device_kind)     # unknown kind: fail before timing
    return {"platform": d0.platform, "device_kind": d0.device_kind,
            "count": len(devices)}


# Rebuild scenario: the worst case — data shards 0-3 lost, survivors
# are shards 4..13; the decode map is the Cauchy inverse restricted to
# the lost rows — a [4, 10] GF matrix, the same kernel shape as encode.
REBUILD_PRESENT = tuple(range(4, 14))
REBUILD_WANTED = (0, 1, 2, 3)


def tpu_phase_gbps(matrix: np.ndarray) -> float:
    """Chained on-device throughput of one [4, 10] GF(2^8) linear map
    (encode or rebuild — both phases are this kernel). Fails without
    an accelerator (require_accelerator)."""
    device = require_accelerator()
    import jax
    import jax.numpy as jnp
    from seaweedfs_tpu.ops.rs_kernel import gf_linear, m2_bits

    m2 = m2_bits(matrix)
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(
        0, 256, size=(DATA_SHARDS, LANES), dtype=np.uint8))
    n_out = matrix.shape[0]
    reps = DATA_SHARDS // n_out + 1              # 4,4,2 rows -> 10

    @jax.jit
    def run(m2, data):
        def body(i, d):
            out = gf_linear(m2, d)               # [4, N] — full map
            fold = jnp.concatenate(
                [out] * reps, axis=0)[:DATA_SHARDS]
            return d ^ fold                      # full-output dependence
        d = jax.lax.fori_loop(0, ITERS, body, data)
        return jnp.sum(d, dtype=jnp.uint32)      # every byte live

    run(m2, data).block_until_ready()            # compile + warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = run(m2, data)
        out.block_until_ready()                  # the sync point
        best = min(best, time.perf_counter() - t0)
        int(out)                                 # keeps every byte live
    total_bytes = DATA_SHARDS * LANES * ITERS
    gbps = total_bytes / best / 1e9
    roofline = device_peaks(device["device_kind"])["hbm_gbps"]
    if gbps >= roofline:
        raise SystemExit(
            f"bench bug: measured {gbps:.0f} GB/s exceeds the "
            f"{roofline:.0f} GB/s single-chip HBM roofline — "
            "the compiler must have elided work; refusing to report")
    return gbps


def _matrices():
    """(encode parity rows, rebuild decode map), both [4, 10] GF(2^8)."""
    from seaweedfs_tpu.ops.rs_code import ReedSolomon, coding_matrix
    rs = ReedSolomon()
    enc = np.asarray(coding_matrix())[DATA_SHARDS:]
    reb = np.asarray(rs.decode_matrix(REBUILD_PRESENT, REBUILD_WANTED))
    return enc, reb


def cpu_phase_gbps(matrix: np.ndarray, backend: str) -> float:
    from seaweedfs_tpu.ops.rs_code import ReedSolomon
    rs = ReedSolomon(backend=backend)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(DATA_SHARDS, CPU_LANES), dtype=np.uint8)
    rs._apply(matrix, data)  # warm (table setup, page-in)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        rs._apply(matrix, data)
        best = min(best, time.perf_counter() - t0)
    return DATA_SHARDS * CPU_LANES / best / 1e9


def _cpu_backend() -> str:
    """The host codec the CPU baseline runs on: the native library,
    built from source on first use — or an error saying why not
    (rs_native.NativeUnavailable), never a silent numpy baseline."""
    from seaweedfs_tpu.native import rs_native
    rs_native.ensure_built()
    return "native"


def _combined(encode_gbps: float, rebuild_gbps: float) -> float:
    """GB/s when every byte is encoded once and rebuilt once (harmonic
    mean): total work 2B over time B/enc + B/reb."""
    return 2.0 / (1.0 / encode_gbps + 1.0 / rebuild_gbps)


def fleet_batch_sweep(batches=(1, 8, 64)) -> dict:
    """Cross-volume fused encode vs serial per-volume encode, end to
    end over real files (the ec/fleet.py scheduler vs a write_ec_files
    loop). This is a HOST-pipeline measurement — reader pool + fused
    dispatch + writer thread — so it runs on the host backend by
    default (override with BENCH_FLEET_BACKEND); the on-device kernel
    rate is the headline metric above. Wall-clock GB/s of .dat bytes,
    best-of-N with the two paths alternated so VM load spikes and page-
    cache writeback stalls hit both — single-shot timings on a shared
    VM swing ±50%, drowning the fused-vs-serial signal (the same
    methodology as the test_perf_gates.py fleet floor).
    """
    import tempfile

    from seaweedfs_tpu.ec import encoder as enc
    from seaweedfs_tpu.ec import fleet

    backend = os.environ.get("BENCH_FLEET_BACKEND") or _cpu_backend()
    vol_mb = int(os.environ.get("BENCH_FLEET_VOL_MB", "8"))
    repeats = int(os.environ.get("BENCH_FLEET_REPEATS", "5"))
    vol_bytes = vol_mb << 20
    block = np.random.default_rng(5).integers(
        0, 256, 4 << 20, dtype=np.uint8).tobytes()
    sweep = []
    for n in batches:
        with tempfile.TemporaryDirectory() as d:
            fused_bases, serial_bases = [], []
            for v in range(n):
                base = os.path.join(d, f"f{v}")
                with open(base + ".dat", "wb") as f:
                    written = 0
                    while written < vol_bytes:
                        written += f.write(block[: vol_bytes - written])
                fused_bases.append(base)
                sbase = os.path.join(d, f"s{v}")
                os.link(base + ".dat", sbase + ".dat")
                serial_bases.append(sbase)
            serial_s, fused_s = [], []
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                for base in serial_bases:
                    enc.write_ec_files(base, backend=backend)
                serial_s.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                fleet.fleet_write_ec_files(fused_bases, backend=backend)
                fused_s.append(time.perf_counter() - t0)
        total_gb = n * vol_bytes / 1e9
        sweep.append({
            "batch_volumes": n,
            "serial_gbps": round(total_gb / min(serial_s), 3),
            "fused_gbps": round(total_gb / min(fused_s), 3),
            "speedup": round(min(serial_s) / min(fused_s), 3),
        })
    return {"metric": "ec_fleet_batch_sweep", "unit": "GB/s",
            "volume_mb": vol_mb, "backend": backend, "sweep": sweep}


def fleet_trace_bench(out_path: str = "bench_trace.json") -> dict:
    """--trace mode: ONE fleet encode with span tracing enabled.

    Writes the Chrome trace-event JSON (chrome://tracing / Perfetto
    loadable) to `out_path` and returns a BENCH line whose `stages`
    field is the per-phase span rollup — stage-level attribution for
    future perf PRs — and whose `value` is the fraction of wall time
    covered by at least one read/dispatch/rs/retire/write span (the
    >=90% acceptance gate: below that, the tracer is missing where
    time goes and its numbers can't be trusted for attribution).
    """
    import tempfile

    from seaweedfs_tpu.ec import fleet
    from seaweedfs_tpu.stats import trace

    backend = os.environ.get("BENCH_FLEET_BACKEND") or _cpu_backend()
    n = int(os.environ.get("BENCH_TRACE_VOLUMES", "8"))
    vol_mb = int(os.environ.get("BENCH_TRACE_VOL_MB", "16"))
    vol_bytes = vol_mb << 20
    block = np.random.default_rng(7).integers(
        0, 256, 4 << 20, dtype=np.uint8).tobytes()
    with tempfile.TemporaryDirectory() as d:
        bases = []
        for v in range(n):
            base = os.path.join(d, f"t{v}")
            with open(base + ".dat", "wb") as f:
                written = 0
                while written < vol_bytes:
                    written += f.write(block[: vol_bytes - written])
            bases.append(base)
        # warm once untraced (page cache, native lib load, thread pools)
        fleet.fleet_write_ec_files(bases[:1], backend=backend)
        trace.enable()
        trace.clear()
        t0 = time.perf_counter()
        fleet.fleet_write_ec_files(bases, backend=backend)
        wall = time.perf_counter() - t0
        spans = trace.spans()
        trace.disable()
    stage_prefixes = ("fleet.read", "fleet.dispatch", "fleet.rs",
                      "fleet.retire", "fleet.write", "fleet.upload")
    covered = trace.busy_union_s(spans, t0, t0 + wall,
                                 prefixes=stage_prefixes)
    with open(out_path, "w") as f:
        json.dump(trace.chrome_trace(), f)
    trace.clear()
    return {
        "metric": "ec_fleet_trace_coverage",
        "value": round(covered / wall, 4),
        "unit": "fraction",
        "coverage_ok": covered / wall >= 0.9,
        "wall_s": round(wall, 4),
        "volumes": n,
        "volume_mb": vol_mb,
        "backend": backend,
        "n_spans": len(spans),
        "stages": trace.rollup(spans),
        "trace_file": out_path,
    }


def mesh_batch_sweep() -> dict:
    """--mesh mode: the unified pod-scale mesh scheduler
    (parallel/mesh_fleet.py, ISSUE 11) vs the per-device fleet
    schedulers (fleet_write_ec_files_sharded) on a forced 8-virtual-
    device CPU mesh, end to end over real files.

    Volumes x size sweep, best-of-N with the two paths alternated
    (same shared-VM methodology as fleet_batch_sweep). BOTH sides ride
    the jax device path — the per-device comparator is exactly the
    pre-PR-11 workaround (N independent schedulers, dispatches pinned
    per chip); a host-native backend would measure a kernel swap, not
    the scheduler. Every config is byte-compared against serial
    write_ec_files across all 14 shards of every volume — a speedup
    over non-identical bytes is worthless. Each mesh row also reports
    dispatch occupancy (live spans per bucket slot, from MeshStats)
    and the overlap fraction: how much of host->device upload time ran
    concurrently with compute/retire/write activity (trace-span
    interval intersection), the double-buffering evidence. B=1 rides
    the pod entry point so the row documents the fallback ladder: path
    "fleet", parity required (the ladder demotes to the SAME
    per-device machinery, so the honest expectation is ~1.0x).
    Volume sizes are in units of one span (10 MB of .dat at the
    default 1 MB small block): sub-span volumes measure lane padding,
    not scheduling.
    """
    import tempfile

    from seaweedfs_tpu.util.cpu_mesh import force_cpu_platform

    n_dev = int(os.environ.get("BENCH_MESH_DEVICES", "8"))
    force_cpu_platform(n_dev)

    from seaweedfs_tpu.ec import encoder as enc
    from seaweedfs_tpu.ec.encoder import shard_file_name
    from seaweedfs_tpu.parallel import (fleet_write_ec_files_sharded,
                                        make_mesh, mesh_write_ec_files,
                                        pod_write_ec_files)
    from seaweedfs_tpu.stats import trace

    repeats = int(os.environ.get("BENCH_MESH_REPEATS", "3"))
    bucket_mb = int(os.environ.get("BENCH_MESH_BUCKET_MB", "32"))
    configs = [tuple(int(x) for x in c.split("x"))
               for c in os.environ.get(
                   "BENCH_MESH_CONFIGS",
                   "1x10,8x10,64x10,16x20").split(",")]
    mesh = make_mesh()
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    block = np.random.default_rng(11).integers(
        0, 256, 4 << 20, dtype=np.uint8).tobytes()

    def fill(base, size):
        with open(base + ".dat", "wb") as f:
            written = 0
            while written < size:
                written += f.write(block[: size - written])

    sweep = []
    for n, vol_mb in configs:
        vol_bytes = vol_mb << 20
        with tempfile.TemporaryDirectory() as d:
            mesh_bases, dev_bases, ref_bases = [], [], []
            for v in range(n):
                # mild size skew so packing sees a real tail, not a
                # uniform slab (same bytes in all three trees)
                size = max(1, vol_bytes - v * 4096)
                base = os.path.join(d, f"m{v}")
                fill(base, size)
                mesh_bases.append(base)
                for prefix, acc in (("d", dev_bases), ("r", ref_bases)):
                    other = os.path.join(d, f"{prefix}{v}")
                    os.link(base + ".dat", other + ".dat")
                    acc.append(other)
            for base in ref_bases:      # byte-identity ground truth
                enc.write_ec_files(base)
            use_pod = n < dp            # the fallback-ladder row
            path, stats = "mesh", None
            dev_s, mesh_s = [], []
            # tiny configs finish in seconds, so relative VM-load noise
            # is largest exactly where the ~1.0x parity claim lives:
            # buy it extra samples
            for _ in range(max(1, repeats * (3 if n == 1 else 1))):
                t0 = time.perf_counter()
                fleet_write_ec_files_sharded(dev_bases, backend="jax")
                dev_s.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                if use_pod:
                    path = pod_write_ec_files(mesh_bases,
                                              backend="jax")
                else:
                    stats = mesh_write_ec_files(mesh_bases, mesh=mesh,
                                                bucket_mb=bucket_mb)
                mesh_s.append(time.perf_counter() - t0)
            for v, base in enumerate(mesh_bases):
                for i in range(14):
                    for got_base in (base, dev_bases[v]):
                        with open(shard_file_name(got_base, i),
                                  "rb") as f:
                            got = f.read()
                        with open(shard_file_name(ref_bases[v], i),
                                  "rb") as f:
                            assert got == f.read(), \
                                f"{got_base} shard {i} != serial"
            row = {
                "volumes": n, "volume_mb": vol_mb, "path": path,
                "per_device_gbps": round(
                    n * vol_bytes / 1e9 / min(dev_s), 3),
                "unified_gbps": round(
                    n * vol_bytes / 1e9 / min(mesh_s), 3),
                "speedup": round(min(dev_s) / min(mesh_s), 3),
                "byte_identical": True,
            }
            if stats is not None:
                row["occupancy"] = round(stats.occupancy, 3)
                row["buckets"] = stats.buckets
                # one extra traced (untimed) mesh pass: how much of
                # upload time ran under compute/retire/write spans
                trace.enable()
                trace.clear()
                t0 = time.perf_counter()
                mesh_write_ec_files(mesh_bases, mesh=mesh,
                                    bucket_mb=bucket_mb)
                t1 = time.perf_counter()
                spans = trace.spans()
                trace.disable()
                trace.clear()
                up = trace.busy_union_s(
                    spans, t0, t1, prefixes=("fleet.upload",))
                rest = ("fleet.rs", "fleet.retire", "fleet.write",
                        "fleet.read")
                busy = trace.busy_union_s(spans, t0, t1, prefixes=rest)
                both = trace.busy_union_s(
                    spans, t0, t1, prefixes=("fleet.upload",) + rest)
                row["overlap_fraction"] = round(
                    (up + busy - both) / up, 3) if up > 0 else 0.0
            sweep.append(row)
    return {"metric": "ec_mesh_batch_sweep", "unit": "GB/s",
            "devices": n_dev, "dp": dp, "sp": sp,
            "bucket_mb": bucket_mb, "sweep": sweep}


def cluster_trace_bench() -> dict:
    """--trace-cluster mode: enabled-path overhead of cluster-wide
    tracing on the data plane, plus one stitched example trace.

    Methodology: the test_data_plane_floor shape (in-process master +
    volume server, run_benchmark_programmatic write+read) best-of-3
    alternated tracer-off vs tracer-on at -trace.sample=1.0 (worst
    case: EVERY request mints ids, buffers spans, and runs the tail
    decision; production tail-only mode does strictly less). The
    enabled/disabled throughput ratio is the BENCH_TRACE.json headline
    — the PR 6-era plane is the 'off' arm measured on the same box, so
    the comparison survives VM-speed drift.
    """
    import io
    import pathlib
    import tempfile

    from seaweedfs_tpu.command.benchmark import run_benchmark_programmatic
    from seaweedfs_tpu.stats import cluster_trace
    from tests.cluster_util import Cluster

    n = int(os.environ.get("BENCH_TRACE_CLUSTER_N", "2000"))

    def one_run(enabled: bool, tmp) -> dict:
        if enabled:
            cluster_trace.enable(sample_fraction=1.0,
                                 slow_threshold_ms=200.0)
        else:
            cluster_trace.disable()
        try:
            c = Cluster(tmp, n_volume_servers=1)
            try:
                r = run_benchmark_programmatic(
                    c.master.url, n=n, concurrency=8, size=1024,
                    do_read=True, out=io.StringIO())
            finally:
                c.stop()
            return {
                "write_rps": r["write"].completed / r["write_seconds"],
                "read_rps": r["read"].completed / r["read_seconds"],
                "failed": r["write"].failed + r["read"].failed,
            }
        finally:
            cluster_trace.disable()
            cluster_trace.reset()

    runs = {"off": [], "on": []}
    with tempfile.TemporaryDirectory() as d:
        i = 0
        for rep in range(3):   # alternate order per the house method
            order = ("off", "on") if rep % 2 == 0 else ("on", "off")
            for arm in order:
                sub = pathlib.Path(d) / f"r{i}"
                sub.mkdir()
                i += 1
                runs[arm].append(one_run(arm == "on", sub))
    best = {arm: {"write_rps": max(x["write_rps"] for x in rs),
                  "read_rps": max(x["read_rps"] for x in rs)}
            for arm, rs in runs.items()}
    failed = sum(x["failed"] for rs in runs.values() for x in rs)
    line = {
        "metric": "cluster_trace_enabled_overhead",
        "unit": "ratio_enabled_over_disabled",
        "n": n,
        "sample": 1.0,
        "failed": failed,
        "disabled": {k: round(v, 1) for k, v in best["off"].items()},
        "enabled": {k: round(v, 1) for k, v in best["on"].items()},
        "write_ratio": round(best["on"]["write_rps"]
                             / best["off"]["write_rps"], 4),
        "read_ratio": round(best["on"]["read_rps"]
                            / best["off"]["read_rps"], 4),
    }
    return line


def scrub_verify_sweep(batches=(1, 8)) -> dict:
    """--scrub mode: integrity-verify throughput of the scrub path.

    The scrub scanner's compute is `fleet_verify_ec_files` — re-encode
    data shards through the fused dispatcher, compare against stored
    parity. This sweep measures end-to-end verify GB/s over real EC
    files (setup cost — the initial encode — excluded), fused many-
    volume verify vs one scheduler per volume, same best-of-N
    alternation discipline as fleet_batch_sweep. GB/s counts the .dat
    bytes whose integrity each pass establishes.
    """
    import tempfile

    from seaweedfs_tpu.ec import encoder as enc
    from seaweedfs_tpu.ec import fleet

    backend = os.environ.get("BENCH_FLEET_BACKEND") or _cpu_backend()
    vol_mb = int(os.environ.get("BENCH_SCRUB_VOL_MB", "8"))
    repeats = int(os.environ.get("BENCH_SCRUB_REPEATS", "5"))
    vol_bytes = vol_mb << 20
    block = np.random.default_rng(9).integers(
        0, 256, 4 << 20, dtype=np.uint8).tobytes()
    sweep = []
    for n in batches:
        with tempfile.TemporaryDirectory() as d:
            bases = []
            for v in range(n):
                base = os.path.join(d, f"v{v}")
                with open(base + ".dat", "wb") as f:
                    written = 0
                    while written < vol_bytes:
                        written += f.write(block[: vol_bytes - written])
                enc.write_ec_files(base, backend=backend)
                bases.append(base)
            serial_s, fused_s = [], []
            clean = True
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                for base in bases:
                    r = fleet.fleet_verify_ec_files([base],
                                                    backend=backend)
                    clean &= all(v.clean for v in r.values())
                serial_s.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                r = fleet.fleet_verify_ec_files(bases, backend=backend)
                clean &= all(v.clean for v in r.values())
                fused_s.append(time.perf_counter() - t0)
        total_gb = n * vol_bytes / 1e9
        sweep.append({
            "batch_volumes": n,
            "serial_gbps": round(total_gb / min(serial_s), 3),
            "fused_gbps": round(total_gb / min(fused_s), 3),
            "speedup": round(min(serial_s) / min(fused_s), 3),
            "all_clean": clean,
        })
    return {"metric": "scrub_verify_gbps", "unit": "GB/s",
            "value": sweep[-1]["fused_gbps"],
            "volume_mb": vol_mb, "backend": backend, "sweep": sweep}


def degraded_read_sweep(batches=(1, 8, 64)) -> dict:
    """--degraded mode: degraded-read serving throughput.

    One EC volume loses 2 data shards; B concurrent readers hammer
    needles whose intervals cross the lost shards. Three paths per B:

      per_interval  the in-place fallback — every reader fetches its
                    own 10 source rows and solves its own one-row
                    reconstruction (the pre-ISSUE-4 shape);
      fused         the DegradedReadFleet — concurrent requests fuse
                    into [B, 10, span] decode dispatches;
      cached        a second pass over the same keys with the tiered
                    read cache warm — hit rate and the throughput a
                    hot degraded range actually serves at.

    Reported as needle reads/s (best-of-N, paths alternated per the
    fleet-sweep methodology — single-shot timings on shared VMs swing
    ±50%).
    """
    import tempfile
    import threading

    from seaweedfs_tpu import ec as ec_mod
    from seaweedfs_tpu.cache import TieredReadCache
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    from seaweedfs_tpu.reads import DegradedReadFleet
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    backend = os.environ.get("BENCH_FLEET_BACKEND") or _cpu_backend()
    n_needles = int(os.environ.get("BENCH_DEGRADED_NEEDLES", "256"))
    needle_kb = int(os.environ.get("BENCH_DEGRADED_NEEDLE_KB", "64"))
    repeats = int(os.environ.get("BENCH_DEGRADED_REPEATS", "3"))
    lost = (0, 3)
    rng = np.random.default_rng(13)
    sweep = []
    with tempfile.TemporaryDirectory() as d:
        v = Volume(d, "", 1)
        payload_bytes = 0
        for i in range(1, n_needles + 1):
            data = rng.integers(0, 256, needle_kb << 10,
                                dtype=np.uint8).tobytes()
            v.write_needle(Needle(id=i, cookie=0xB0, data=data))
            payload_bytes += len(data)
        v.close()
        base = os.path.join(d, "1")
        ec_mod.write_ec_files(base, backend=backend)
        ec_mod.write_sorted_file_from_idx(base)
        ecv = EcVolume(d, "", 1)
        for i in range(14):
            if i not in lost:
                ecv.mount_shard(i)

        def run_readers(b, keys, decoder=None, cache=None):
            """b threads split `keys`; returns wall seconds."""
            errs = []
            chunks = [keys[i::b] for i in range(b)]

            def worker(mine):
                try:
                    for k in mine:
                        if cache is not None:
                            from seaweedfs_tpu.ec import store_ec

                            class _S:
                                def find_ec_volume(self, vid):
                                    return ecv
                            store_ec.read_ec_needle(
                                _S(), 1, Needle(id=k, cookie=0xB0),
                                cache=cache, decoder=decoder)
                        else:
                            ecv.read_needle(Needle(id=k, cookie=0xB0),
                                            decoder=decoder)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(ch,))
                  for ch in chunks if ch]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errs:
                raise errs[0]
            return time.perf_counter() - t0

        keys = list(range(1, n_needles + 1))
        for b in batches:
            serial_s, fused_s = [], []
            fleet = DegradedReadFleet(backend=backend,
                                      batch_window_s=0.004)
            for _ in range(max(1, repeats)):
                serial_s.append(run_readers(b, keys))
                fused_s.append(run_readers(b, keys, decoder=fleet))
            occupancy = fleet.spans_decoded / max(1, fleet.dispatches)
            # cache pass: cold fill, then hot re-read (hit rate is the
            # HOT pass's — the steady state a hot degraded range sees)
            cache = TieredReadCache(1 << 30)
            run_readers(b, keys, decoder=fleet, cache=cache)
            h0, m0 = cache.hits, cache.misses
            hot_s = run_readers(b, keys, decoder=fleet, cache=cache)
            dh, dm = cache.hits - h0, cache.misses - m0
            hit_rate = dh / max(1, dh + dm)
            fleet.stop()
            sweep.append({
                "concurrency": b,
                "per_interval_reads_s":
                    round(len(keys) / min(serial_s), 1),
                "fused_reads_s": round(len(keys) / min(fused_s), 1),
                "speedup": round(min(serial_s) / min(fused_s), 3),
                "fused_batch_occupancy": round(occupancy, 2),
                "cached_reads_s": round(len(keys) / hot_s, 1),
                "cache_hit_rate": round(hit_rate, 4),
            })
        ecv.close()
    return {"metric": "degraded_read_sweep", "unit": "reads/s",
            "needles": n_needles, "needle_kb": needle_kb,
            "lost_shards": list(lost), "backend": backend,
            "sweep": sweep}


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_server(*args):
    """One real `python -m seaweedfs_tpu <role> ...` subprocess (the
    bench_profile.py pattern, shared by the ingest and lifecycle
    sweeps — in-process servers would share the client's GIL)."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", *args],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        cwd=REPO_ROOT, env=env)


def _wait_http(url, timeout=60.0):
    import urllib.request
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2):
                return
        except OSError:
            time.sleep(0.2)
    raise RuntimeError(f"server at {url} never came up")


def ingest_pipeline_sweep(chunk_counts=(1, 8, 64),
                          replications=("000", "010")) -> dict:
    """--ingest mode: filer multi-chunk upload throughput.

    The master and 2 volume servers (racks r0/r1) run as REAL CLI
    subprocesses (the bench_profile.py pattern) — in-process servers
    would share the ingest client's GIL and hide exactly the overlap
    this sweep measures. The filer ingest path itself runs in-process
    as the client under test; per (chunk count x replication) cell two
    paths upload the same body straight through
    FilerServer.upload_to_chunks:

      serial     -ingest.parallelism 1, no lease cache — one master
                 assign + one blocking volume upload per chunk (the
                 pre-ISSUE-5 shape);
      pipelined  -ingest.parallelism 8 + -assign.leaseCount 16 —
                 chunk k+1 sliced while k-w..k upload concurrently,
                 assigns amortized count=N.

    Reported as uploads of the whole body per second (best-of-N,
    paths alternated per the fleet-sweep methodology — single-shot
    timings on shared VMs swing ±50%), plus master assign round trips
    per body on each path.
    """
    import subprocess
    import tempfile

    from seaweedfs_tpu.operation.assign_lease import LeaseCache
    from seaweedfs_tpu.server.filer import FilerServer

    chunk_kb = int(os.environ.get("BENCH_INGEST_CHUNK_KB", "64"))
    repeats = int(os.environ.get("BENCH_INGEST_REPEATS", "3"))
    parallelism = int(os.environ.get("BENCH_INGEST_PARALLELISM", "8"))
    lease_count = int(os.environ.get("BENCH_INGEST_LEASES", "16"))
    free_port, spawn, wait_http = _free_port, _spawn_server, _wait_http

    rng = np.random.default_rng(29)
    sweep = []
    procs = []
    with tempfile.TemporaryDirectory() as d:
        mport = free_port()
        master_url = f"127.0.0.1:{mport}"
        try:
            procs.append(spawn("master", "-port", str(mport),
                               "-mdir", os.path.join(d, "m"),
                               "-volumeSizeLimitMB", "256",
                               "-pulseSeconds", "0.3"))
            wait_http(f"http://{master_url}/cluster/status")
            for i, rack in enumerate(("r0", "r1")):
                vport = free_port()
                procs.append(spawn(
                    "volume", "-port", str(vport),
                    "-dir", os.path.join(d, f"v{i}"), "-max", "200",
                    "-rack", rack, "-mserver", master_url,
                    "-pulseSeconds", "0.3"))
                wait_http(f"http://127.0.0.1:{vport}/status")
            time.sleep(1.0)   # first heartbeats register the nodes

            fs = FilerServer(master_url=master_url, port=free_port(),
                             chunk_size=chunk_kb << 10,
                             ingest_parallelism=parallelism)

            def run_one(n_chunks, replication, pipelined):
                body = rng.integers(0, 256, n_chunks * (chunk_kb << 10),
                                    dtype=np.uint8).tobytes()
                if pipelined:
                    fs.ingest_parallelism = parallelism
                    fs.leases = LeaseCache(count=lease_count) \
                        if lease_count > 1 else None
                else:
                    fs.ingest_parallelism = 1
                    fs.leases = None
                t0 = time.perf_counter()
                chunks = fs.upload_to_chunks(body,
                                             replication=replication)
                dt = time.perf_counter() - t0
                assert len(chunks) == n_chunks
                assigns = fs.leases.assign_round_trips if fs.leases \
                    else n_chunks
                return dt, assigns

            for replication in replications:
                for n_chunks in chunk_counts:
                    run_one(n_chunks, replication, False)  # warm vols
                    serial_s, piped_s = [], []
                    serial_assigns = piped_assigns = 0
                    for _ in range(max(1, repeats)):  # alternate: load
                        # spikes hit both paths
                        dt, serial_assigns = run_one(
                            n_chunks, replication, pipelined=False)
                        serial_s.append(dt)
                        dt, piped_assigns = run_one(
                            n_chunks, replication, pipelined=True)
                        piped_s.append(dt)
                    mb = n_chunks * chunk_kb / 1024
                    sweep.append({
                        "chunks": n_chunks,
                        "replication": replication,
                        "serial_uploads_s": round(1 / min(serial_s), 2),
                        "pipelined_uploads_s":
                            round(1 / min(piped_s), 2),
                        "serial_mb_s": round(mb / min(serial_s), 1),
                        "pipelined_mb_s": round(mb / min(piped_s), 1),
                        "speedup":
                            round(min(serial_s) / min(piped_s), 3),
                        "serial_assigns": serial_assigns,
                        "pipelined_assigns": piped_assigns,
                    })
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    headline = max((row["speedup"] for row in sweep
                    if row["chunks"] == max(chunk_counts)),
                   default=0.0)
    return {"metric": "ingest_pipeline_sweep", "unit": "uploads/s",
            "chunk_kb": chunk_kb, "parallelism": parallelism,
            "lease_count": lease_count,
            "value": headline, "sweep": sweep}


def meta_plane_sweep(fanouts=(64, 512), reader_counts=(1, 8)) -> dict:
    """--meta mode: metadata-plane throughput (ISSUE 12) against REAL
    CLI subprocesses — in-process servers would share the client's GIL
    and hide exactly the round-trip elimination this sweep measures.

    Two halves:

      lookup   the 64-chunk-file read workload's lookups: resolve the
               same 64 distinct vids (a) singly — one gRPC
               LookupVolume per vid, the pre-ISSUE-12 shape — and
               (b) through the armed coalescing cache, whose misses
               fuse into batched /dir/lookup?volumeIds= round trips
               (the cache is RESET before every timed batched run, so
               the number measures batching+coalescing, not TTL
               hits; the hot row measures the hits). Repeated with R
               concurrent readers so single-flight + coalescing see
               contention. Best-of-N, paths alternated per house
               style.

      listing  directory fan-out F x concurrent readers R against two
               filer subprocesses on the same master — one default,
               one with -meta.listingCacheMB 64 — plus the
               correctness probes: the hit-path listing body must be
               byte-identical to the miss-path body, and a listing
               taken immediately after a cache-invalidating mutation
               must show the mutation.
    """
    import json as json_mod
    import subprocess
    import tempfile
    import threading
    import urllib.request

    sys.path.insert(0, REPO_ROOT)
    from seaweedfs_tpu.operation import operations
    from seaweedfs_tpu.util import http_client
    from seaweedfs_tpu.wdclient import lookup_cache

    n_vids = int(os.environ.get("BENCH_META_VIDS", "64"))
    repeats = int(os.environ.get("BENCH_META_REPEATS", "3"))
    listings_per_reader = int(os.environ.get("BENCH_META_LISTINGS", "40"))
    free_port, spawn, wait_http = _free_port, _spawn_server, _wait_http

    out = {"metric": "meta_plane_sweep", "vids": n_vids,
           "lookup": [], "listing": []}
    procs = []
    with tempfile.TemporaryDirectory() as d:
        mport = free_port()
        master_url = f"127.0.0.1:{mport}"
        try:
            procs.append(spawn("master", "-port", str(mport),
                               "-mdir", os.path.join(d, "m"),
                               "-volumeSizeLimitMB", "64",
                               "-pulseSeconds", "0.3"))
            wait_http(f"http://{master_url}/cluster/status")
            vport = free_port()
            procs.append(spawn("volume", "-port", str(vport),
                               "-dir", os.path.join(d, "v"),
                               "-max", str(n_vids + 8),
                               "-mserver", master_url,
                               "-pulseSeconds", "0.3"))
            wait_http(f"http://127.0.0.1:{vport}/status")
            time.sleep(1.0)   # first heartbeats register the node

            with urllib.request.urlopen(
                    f"http://{master_url}/vol/grow?count={n_vids}",
                    timeout=30) as r:
                grown = json_mod.loads(r.read())
            vids = grown.get("volumeIds") or []
            assert len(vids) >= n_vids, grown

            def run_singly(readers: int) -> float:
                lookup_cache.reset()

                def worker():
                    for vid in vids:
                        operations.lookup(master_url, vid)
                t0 = time.perf_counter()
                ts = [threading.Thread(target=worker)
                      for _ in range(readers)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                return time.perf_counter() - t0

            def run_batched(readers: int, hot: bool = False) -> float:
                lookup_cache.reset()
                lookup_cache.configure(enable=True, ttl_s=30.0,
                                       coalesce_ms=2.0)
                if hot:
                    operations.lookup_many(master_url, vids)

                def worker():
                    operations.lookup_many(master_url, vids)
                t0 = time.perf_counter()
                ts = [threading.Thread(target=worker)
                      for _ in range(readers)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                dt = time.perf_counter() - t0
                lookup_cache.reset()
                return dt

            operations.lookup(master_url, vids[0])   # warm stubs/pool
            for readers in reader_counts:
                singly_s, batched_s, hot_s = [], [], []
                for _ in range(max(1, repeats)):   # alternated
                    singly_s.append(run_singly(readers))
                    batched_s.append(run_batched(readers))
                    hot_s.append(run_batched(readers, hot=True))
                total = len(vids) * readers
                out["lookup"].append({
                    "readers": readers,
                    "singly_lookups_s":
                        round(total / min(singly_s), 1),
                    "batched_lookups_s":
                        round(total / min(batched_s), 1),
                    "hot_lookups_s": round(total / min(hot_s), 1),
                    "speedup":
                        round(min(singly_s) / min(batched_s), 3),
                })

            # -- listing half --------------------------------------------------
            fports = {}
            for tag, extra in (("off", []),
                               ("on", ["-meta.listingCacheMB", "64"])):
                fport = free_port()
                fports[tag] = fport
                procs.append(spawn(
                    "filer", "-port", str(fport), "-master", master_url,
                    "-store", "sqlite",
                    "-dir", os.path.join(d, f"f-{tag}"), *extra))
                wait_http(f"http://127.0.0.1:{fport}/")

            blob = b"meta-bench" * 10
            for fanout in fanouts:
                for tag, fport in fports.items():
                    for i in range(fanout):
                        r = http_client.request(
                            "POST",
                            f"127.0.0.1:{fport}/bench{fanout}/f{i:04d}",
                            body=blob)
                        assert r.status == 201, (tag, r.status)

                def list_once(fport, fanout):
                    r = http_client.request(
                        "GET",
                        f"127.0.0.1:{fports[fport]}/bench{fanout}/"
                        f"?limit=2048",
                        headers={"Accept": "application/json"})
                    assert r.status == 200, r.status
                    return r.body

                # byte-identity: miss-path body (first ever listing)
                # vs hit-path body on the SAME filer
                miss_body = list_once("on", fanout)
                hit_body = list_once("on", fanout)
                assert miss_body == hit_body, \
                    "listing hit bytes differ from miss bytes"

                def run_listings(tag, readers) -> float:
                    def worker():
                        for _ in range(listings_per_reader):
                            list_once(tag, fanout)
                    t0 = time.perf_counter()
                    ts = [threading.Thread(target=worker)
                          for _ in range(readers)]
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join()
                    return time.perf_counter() - t0

                for readers in reader_counts:
                    off_s, on_s = [], []
                    for _ in range(max(1, repeats)):   # alternated
                        off_s.append(run_listings("off", readers))
                        on_s.append(run_listings("on", readers))
                    total = listings_per_reader * readers
                    out["listing"].append({
                        "fanout": fanout, "readers": readers,
                        "store_listings_s":
                            round(total / min(off_s), 1),
                        "cached_listings_s":
                            round(total / min(on_s), 1),
                        "speedup": round(min(off_s) / min(on_s), 3),
                    })

                # correctness: a cache-invalidating mutation must be
                # visible in the very next listing
                r = http_client.request(
                    "POST",
                    f"127.0.0.1:{fports['on']}/bench{fanout}/zz-new",
                    body=blob)
                assert r.status == 201, r.status
                fresh = json_mod.loads(list_once("on", fanout))
                names = [e["FullPath"].rsplit("/", 1)[1]
                         for e in fresh["Entries"]]
                assert "zz-new" in names, \
                    "listing after mutation is stale"
                out.setdefault("correct_after_mutation", True)

            # metadata-layer cost per fanout: the end-to-end HTTP rows
            # above are dominated by JSON render + socket work, which
            # masks what the cache changes — time Filer.list_entries
            # itself (store walk vs page hit; the hit never touches
            # the store, which is the whole point on redis/mysql-class
            # stores where a walk is a network round trip)
            from seaweedfs_tpu.filer import Filer, SqliteStore
            from seaweedfs_tpu.filer.filer import new_entry
            from seaweedfs_tpu.filer.listing_cache import ListingCache
            for fanout in fanouts:
                f = Filer(SqliteStore(
                    os.path.join(d, f"meta-{fanout}.db")))
                for i in range(fanout):
                    f.create_entry("/b", new_entry(f"f{i:04d}"))

                def timed(fn, n=200):
                    fn()
                    t0 = time.perf_counter()
                    for _ in range(n):
                        fn()
                    return (time.perf_counter() - t0) / n * 1e6

                walk_us = timed(
                    lambda: f.list_entries("/b", limit=2048))
                f.attach_listing_cache(ListingCache(64 << 20))
                hit_us = timed(
                    lambda: f.list_entries("/b", limit=2048))
                assert f.listing_cache.stats()["hits"] >= 200
                f.close()
                out.setdefault("listing_meta_layer", []).append({
                    "fanout": fanout,
                    "store_walk_us": round(walk_us),
                    "cache_hit_us": round(hit_us),
                    "speedup": round(walk_us / hit_us, 3),
                })
        finally:
            lookup_cache.reset()
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    headline = max((row["speedup"] for row in out["lookup"]),
                   default=0.0)
    out["unit"] = "speedup"
    out["value"] = headline
    return out


def _serve_pump(port: int, fid: str, n_conns: int, seconds: float,
                expect_bytes: int) -> dict:
    """Single-threaded selector client: n_conns keep-alive
    connections each issue GET /fid, read the full response, repeat.
    One thread drives all of them, so at 256 connections the CLIENT
    is not the thing being measured. Returns reqs + errors."""
    import selectors
    import socket

    req = (f"GET /{fid} HTTP/1.1\r\nHost: b\r\n\r\n").encode()
    sel = selectors.DefaultSelector()

    class C:
        __slots__ = ("sock", "buf", "need", "reqs")

        def __init__(self):
            self.sock = socket.create_connection(("127.0.0.1", port))
            self.sock.setblocking(False)
            self.buf = bytearray()
            self.need = -1
            self.reqs = 0

    conns = []
    for _ in range(n_conns):
        c = C()
        conns.append(c)
        sel.register(c.sock, selectors.EVENT_READ, c)
        try:
            c.sock.sendall(req)
        except BlockingIOError:
            pass
    done = 0
    errors = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        for key, _mask in sel.select(0.1):
            c = key.data
            try:
                data = c.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError:
                errors += 1
                sel.unregister(c.sock)
                continue
            if not data:
                errors += 1
                sel.unregister(c.sock)
                continue
            c.buf += data
            if c.need < 0:
                end = c.buf.find(b"\r\n\r\n")
                if end < 0:
                    continue
                head = bytes(c.buf[:end]).lower()
                i = head.find(b"content-length:")
                j = head.find(b"\r", i)
                clen = int(head[i + 15:j if j > 0 else len(head)])
                c.need = end + 4 + clen
            if len(c.buf) >= c.need:
                del c.buf[:c.need]
                c.need = -1
                c.reqs += 1
                done += 1
                try:
                    c.sock.sendall(req)
                except OSError:
                    errors += 1
                    sel.unregister(c.sock)
    wall = time.perf_counter() - t0
    for c in conns:
        try:
            c.sock.close()
        except OSError:
            pass
    sel.close()
    return {"reqs": done, "wall_s": round(wall, 3),
            "rps": round(done / wall, 1),
            "mb_s": round(done * expect_bytes / wall / 1e6, 1),
            "errors": errors,
            "active_conns": len([c for c in conns if c.reqs > 0])}


def serve_async_sweep(seconds: float = 3.0, rounds: int = 3) -> dict:
    """--serve mode: threaded vs async serving core on a REAL volume
    server subprocess (ISSUE 13). Three workloads per model: small-GET
    throughput at 8 keep-alive connections, 1MB-GET throughput at 4
    (the zero-copy sendfile path), and keep-alive SCALING at 256
    connections — the regime where thread-per-connection parks 256
    threads and the selector loop parks none. Best-of-N alternated
    (shared-VM timing discipline)."""
    import urllib.request

    results = {"metric": "serve_async", "unit": "req/s",
               "seconds_per_round": seconds, "rounds": rounds}
    blobs = {}

    def boot(model):
        mport, vport = _free_port(), _free_port()
        extra = ["-serve.async"] if model == "async" else []
        m = _spawn_server("master", "-port", str(mport),
                          "-volumeSizeLimitMB", "256")
        v = _spawn_server("volume", "-port", str(vport),
                          "-dir", f"/tmp/bench-serve-{model}-{vport}",
                          "-mserver", f"127.0.0.1:{mport}",
                          "-max", "8", *extra)
        _wait_http(f"http://127.0.0.1:{mport}/dir/status")
        _wait_http(f"http://127.0.0.1:{vport}/status")
        for name, size in (("small", 4096), ("large", 1 << 20)):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}/dir/assign") as r:
                a = json.load(r)
            body = os.urandom(size)
            bnd = "b0und"
            payload = ((f"--{bnd}\r\nContent-Disposition: form-data;"
                        f' name="file"; filename="{name}"\r\n\r\n')
                       .encode() + body +
                       f"\r\n--{bnd}--\r\n".encode())
            rq = urllib.request.Request(
                f"http://{a['url']}/{a['fid']}", data=payload,
                method="POST",
                headers={"Content-Type":
                         f"multipart/form-data; boundary={bnd}"})
            with urllib.request.urlopen(rq):
                pass
            blobs[name] = (a["fid"], size)
        return m, v, vport

    workloads = (("small_get_c8", "small", 8),
                 ("large_get_c4", "large", 4),
                 ("scale_c256", "small", 256))
    best = {model: {w: None for w, _, _ in workloads}
            for model in ("threaded", "async")}
    for rnd in range(rounds):
        order = ("threaded", "async") if rnd % 2 == 0 \
            else ("async", "threaded")
        for model in order:
            m = v = None
            try:
                m, v, vport = boot(model)
                for wname, blob, conns in workloads:
                    fid, size = blobs[blob]
                    line = _serve_pump(vport, fid, conns, seconds,
                                       size)
                    prev = best[model][wname]
                    if prev is None or line["rps"] > prev["rps"]:
                        best[model][wname] = line
            finally:
                for proc in (v, m):
                    if proc is not None:
                        proc.terminate()
                for proc in (v, m):
                    if proc is not None:
                        proc.wait(timeout=10)
    results["threaded"] = best["threaded"]
    results["async"] = best["async"]
    results["speedup"] = {
        w: round(best["async"][w]["rps"] /
                 max(best["threaded"][w]["rps"], 1e-9), 3)
        for w, _, _ in workloads}
    return results


def chaos_sweep() -> dict:
    """Resilience scenario sweep (ISSUE 6 satellite): an in-process
    master + 3 volume servers take concurrent reads while the sweep
    kills a replica, stalls a volume, and flaps the master. Per
    scenario: p50/p99 latency + error rate. The point is the SHAPE —
    failures must cost bounded latency (fail fast / hedge / fail over),
    never hangs — so the gate is error-rate and tail bounds, not
    throughput.

    Scenarios:
      healthy           baseline tail
      kill_one_replica  one replica REALLY stopped; reads fail over,
                        breakers turn the dead peer into a fast skip
      slow_one_shard    one volume's reads stalled 200ms server-side;
                        hedged reads bound the tail
      flapping_master   master restarted mid-load; lookup-dependent
                        reads ride the jittered deadline-capped retry
    """
    import tempfile
    import threading

    sys.path.insert(0, REPO_ROOT)
    from tests.cluster_util import Cluster

    from seaweedfs_tpu.resilience import Hedger, breaker, deadline, \
        failpoint
    from seaweedfs_tpu.util import http_client
    from seaweedfs_tpu.util.retry import retry

    n_threads = int(os.environ.get("BENCH_CHAOS_THREADS", "8"))
    reads_per_thread = int(os.environ.get("BENCH_CHAOS_READS", "40"))
    cookie = 0xBE9CBE9C

    def fid(vid, key):
        return f"{vid},{key:x}{cookie:08x}"

    def run_scenario(read_one, keys):
        lats, errs, lock = [], [], threading.Lock()

        def worker(widx):
            for it in range(reads_per_thread):
                key = keys[(widx + it) % len(keys)]
                t0 = time.perf_counter()
                try:
                    read_one(key)
                except Exception as e:  # noqa: BLE001 - counted
                    with lock:
                        errs.append(repr(e))
                    continue
                with lock:
                    lats.append(time.perf_counter() - t0)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * reads_per_thread
        ordered = sorted(lats) or [0.0]

        def pct(q):
            return round(
                ordered[min(len(ordered) - 1, int(q * len(ordered)))]
                * 1000, 2)

        return {"n": total, "p50_ms": pct(0.5), "p99_ms": pct(0.99),
                "max_ms": round(ordered[-1] * 1000, 2),
                "error_rate": round(len(errs) / total, 4),
                "sample_error": errs[0][:120] if errs else ""}

    out = {"metric": "chaos_sweep", "threads": n_threads,
           "scenarios": {}}
    with tempfile.TemporaryDirectory() as td:
        import pathlib
        cluster = Cluster(pathlib.Path(td), n_volume_servers=3,
                          racks=["r1", "r2", "r3"])
        stopped = []
        try:
            vs0, vs1, vs2 = cluster.volume_servers
            for vid, servers in ((301, [vs0, vs1]), (302, [vs0, vs2])):
                for vs in servers:
                    vs.store.add_volume(vid, "",
                                        replica_placement="010")
                    vs.trigger_heartbeat()
            cluster.wait_for(
                lambda: all(len(cluster.master.topo.lookup(v)) == 2
                            for v in (301, 302)),
                what="volume registration")
            blob = os.urandom(4096)
            keys = list(range(1, 9))
            for vid, primary in ((301, vs0), (302, vs0)):
                for k in keys:
                    r = http_client.request(
                        "POST", f"{primary.url}/{fid(vid, k)}",
                        body=blob)
                    assert r.status == 201, r.status

            breaker.configure(enable=True, threshold=3, cooldown_s=1.0)

            def make_reader(name):
                # one hedger per scenario so budget/win accounting in
                # the emitted JSON is per-scenario, not cumulative
                hedger = Hedger(delay_floor_s=0.02, max_inflight=64,
                                name=name)

                def hedged_read(vid, key, candidates):
                    with deadline.budget(5.0):
                        urls = breaker.sort_candidates(candidates)

                        def one(u):
                            r = http_client.request(
                                "GET", f"{u}/{fid(vid, key)}",
                                timeout=4.0)
                            if r.status != 200:
                                raise IOError(f"http {r.status}")
                            if r.body != blob:
                                raise IOError("bytes differ")
                            return r.body
                        return hedger.fetch(
                            [lambda u=u: one(u) for u in urls])
                return hedger, hedged_read

            _, read_healthy = make_reader("bench-healthy")
            out["scenarios"]["healthy"] = run_scenario(
                lambda k: read_healthy(301, k, [vs0.url, vs1.url]),
                keys)

            vs1.stop()
            stopped.append(vs1)
            http_client.close_all()
            _, read_kill = make_reader("bench-kill")
            out["scenarios"]["kill_one_replica"] = run_scenario(
                lambda k: read_kill(301, k, [vs1.url, vs0.url]), keys)

            # slow-one-shard at the hedge design point: ~4% of traffic
            # hits the stalled volume (hedging's 5% budget is sized for
            # the p95 tail, not for a workload that is ALL stall — at
            # higher stall shares the budget correctly caps hedges and
            # the tail sits at the stall latency)
            failpoint.arm("volume.read", "delay", arg=0.2,
                          match={"server": vs2.url, "vid": "302"})
            hedger3, read_slow = make_reader("bench-slow")

            def mixed_read(k):
                if k == 0:
                    return read_slow(302, keys[0],
                                     [vs2.url, vs0.url])
                return read_slow(301, keys[k % len(keys)], [vs0.url])

            out["scenarios"]["slow_one_shard"] = run_scenario(
                mixed_read, list(range(25)))
            failpoint.disarm()
            out["scenarios"]["slow_one_shard"]["hedges"] = \
                hedger3.hedges
            out["scenarios"]["slow_one_shard"]["hedge_wins"] = \
                hedger3.wins
            out["scenarios"]["slow_one_shard"]["hedge_requests"] = \
                hedger3.requests

            # flapping master: down for ~0.5s mid-load; lookups ride
            # the jittered retry with a 2s deadline cap
            from seaweedfs_tpu.operation import operations

            def lookup_read(k):
                urls = retry(
                    "bench.lookup",
                    lambda: operations.lookup(
                        cluster.master.url, 301),
                    times=4, wait_seconds=0.05, deadline=2.0)
                for u in breaker.sort_candidates(urls):
                    r = http_client.request("GET",
                                            f"{u}/{fid(301, k)}",
                                            timeout=2.0)
                    if r.status == 200:
                        return
                raise IOError("no replica")

            def flap():
                time.sleep(0.4)
                cluster.master.stop()
                from seaweedfs_tpu import rpc as rpc_mod
                rpc_mod.close_channels()
                time.sleep(0.5)
                from seaweedfs_tpu.server.master import MasterServer
                m2 = MasterServer(
                    port=cluster.master.port,
                    meta_dir=os.path.join(td, "master2"),
                    pulse_seconds=0.2)
                for _ in range(50):
                    try:
                        m2.start()
                        break
                    except OSError:
                        time.sleep(0.2)
                cluster.master = m2

            flapper = threading.Thread(target=flap)
            flapper.start()
            out["scenarios"]["flapping_master"] = run_scenario(
                lookup_read, keys)
            flapper.join()
        finally:
            failpoint.disarm()
            breaker.reset()
            cluster.volume_servers = [
                v for v in cluster.volume_servers if v not in stopped]
            cluster.stop()
    return out


def lifecycle_sweep() -> dict:
    """--lifecycle mode (ISSUE 9): a synthetic diurnal workload against
    a REAL 3-server subprocess cluster with the policy engine on.

    Shape: two volumes — HOT takes a steady read stream throughout;
    COLD is written once and then left idle ("night"). The sweep
    asserts the acceptance contract end to end: the idle volume is
    EC-encoded by the policy loop with no operator action, sustained
    reads ("morning") bring it back to a replicated volume, reads are
    byte-identical across both transitions, and the hot volume's read
    p99 while transitions run (under the byte-budget throttle) stays
    within a generous factor of its pre-transition p99.
    """
    import subprocess
    import tempfile
    import urllib.request

    pulse = float(os.environ.get("BENCH_LIFECYCLE_PULSE", "0.3"))
    heat_window = float(os.environ.get("BENCH_LIFECYCLE_WINDOW", "2.0"))
    hot_dwell = float(os.environ.get("BENCH_LIFECYCLE_DWELL", "3.0"))
    n_keys = int(os.environ.get("BENCH_LIFECYCLE_KEYS", "16"))
    blob_kb = int(os.environ.get("BENCH_LIFECYCLE_BLOB_KB", "64"))
    free_port, spawn, wait_http = _free_port, _spawn_server, _wait_http

    def http_json(url, method="GET", timeout=10.0):
        req = urllib.request.Request(f"http://{url}", method=method)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    def normal_and_ec_vids(master_url):
        topo = http_json(f"{master_url}/dir/status")["Topology"]
        normal, ec = set(), set()
        for dc in topo["data_centers"]:
            for rack in dc["racks"]:
                for node in rack["nodes"]:
                    normal.update(v["id"] for v in node["volumes"])
                    ec.update(e["id"] for e in node["ec_shards"])
        return normal, ec

    def pct(ordered, q):
        return ordered[min(len(ordered) - 1, int(q * len(ordered)))]

    cookie = 0x11CEC1E5
    blob = os.urandom(blob_kb << 10)

    def fid(vid, key):
        return f"{vid},{key:x}{cookie:08x}"

    def read_one(master_url, vid, key, timeout=5.0):
        lk = http_json(f"{master_url}/dir/lookup?volumeId={vid}")
        url = lk["locations"][0]["url"]
        with urllib.request.urlopen(
                f"http://{url}/{fid(vid, key)}", timeout=timeout) as r:
            return r.read()

    procs = []
    out = {"metric": "lifecycle_diurnal", "unit": "ratio",
           "heat_window_s": heat_window, "hot_dwell_s": hot_dwell}
    with tempfile.TemporaryDirectory() as d:
        mport = free_port()
        master_url = f"127.0.0.1:{mport}"
        try:
            procs.append(spawn(
                "master", "-port", str(mport),
                "-mdir", os.path.join(d, "m"),
                "-volumeSizeLimitMB", "64",
                "-pulseSeconds", str(pulse),
                "-lifecycle",
                "-lifecycle.intervalSeconds", "0.5",
                "-lifecycle.coolThreshold", "0.5",
                "-lifecycle.warmThreshold", "5",
                "-lifecycle.hotDwellSeconds", str(hot_dwell),
                "-lifecycle.warmDwellSeconds", "1.0",
                "-lifecycle.coldDwellSeconds", "1.0",
                "-lifecycle.maxInflight", "4",
                "-lifecycle.throttleMBps", "64"))
            wait_http(f"http://{master_url}/cluster/status")
            for i in range(3):
                vport = free_port()
                procs.append(spawn(
                    "volume", "-port", str(vport),
                    "-dir", os.path.join(d, f"v{i}"), "-max", "50",
                    "-mserver", master_url,
                    "-pulseSeconds", str(pulse),
                    "-heat.track",
                    "-heat.windowSeconds", str(heat_window)))
                wait_http(f"http://127.0.0.1:{vport}/status")
            time.sleep(pulse * 3)   # heartbeats register the nodes

            grown = http_json(
                f"{master_url}/vol/grow?count=2&replication=000",
                method="POST")["volumeIds"]
            hot_vid, cold_vid = grown[0], grown[1]
            for vid in (hot_vid, cold_vid):
                lk = http_json(f"{master_url}/dir/lookup?volumeId={vid}")
                url = lk["locations"][0]["url"]
                for k in range(1, n_keys + 1):
                    req = urllib.request.Request(
                        f"http://{url}/{fid(vid, k)}", data=blob,
                        method="POST")
                    with urllib.request.urlopen(req, timeout=10) as r:
                        r.read()

            # "day": steady hot reads, pre-transition p99 baseline
            def hot_read_window(seconds):
                lats = []
                stop = time.monotonic() + seconds
                k = 0
                while time.monotonic() < stop:
                    k = k % n_keys + 1
                    t0 = time.perf_counter()
                    got = read_one(master_url, hot_vid, k)
                    lats.append(time.perf_counter() - t0)
                    assert got == blob, "hot read bytes differ"
                return sorted(lats)

            base = hot_read_window(3.0)
            out["hot_p99_before_ms"] = round(pct(base, 0.99) * 1000, 2)

            # "night": cold volume idles past dwell; keep the hot one
            # hot while the engine encodes — p99 measured DURING
            encode_t0 = time.monotonic()
            during = []
            encoded = False
            while time.monotonic() - encode_t0 < 90:
                during.extend(hot_read_window(1.0))
                normal, ec = normal_and_ec_vids(master_url)
                if cold_vid in ec and cold_vid not in normal:
                    encoded = True
                    break
            out["encode_s"] = round(time.monotonic() - encode_t0, 1)
            during.sort()
            out["hot_p99_during_ms"] = round(pct(during, 0.99) * 1000, 2)
            if not encoded:
                raise SystemExit(
                    "cold volume was never EC-encoded by the policy "
                    "loop")
            # byte-identity on the now-WARM volume
            assert read_one(master_url, cold_vid, 1) == blob, \
                "post-encode read bytes differ"

            # "morning": sustained reads re-heat the cold volume until
            # the engine decodes it back to a replicated volume
            decode_t0 = time.monotonic()
            decoded = False
            k = 0
            while time.monotonic() - decode_t0 < 90:
                for _ in range(8):
                    k = k % n_keys + 1
                    try:
                        got = read_one(master_url, cold_vid, k,
                                       timeout=3.0)
                        assert got == blob, "re-heat read bytes differ"
                    except OSError:
                        pass   # mid-decode blip: shards unmounting
                normal, ec = normal_and_ec_vids(master_url)
                if cold_vid in normal and cold_vid not in ec:
                    decoded = True
                    break
                time.sleep(0.2)
            out["decode_s"] = round(time.monotonic() - decode_t0, 1)
            if not decoded:
                raise SystemExit(
                    "re-heated volume never returned to replicated "
                    "form")
            for k in range(1, n_keys + 1):
                assert read_one(master_url, cold_vid, k) == blob, \
                    "post-decode read bytes differ"

            st = http_json(f"{master_url}/cluster/lifecycle")
            out["transitions_ok"] = st.get("transitions_ok", 0)
            out["passes"] = st.get("passes", 0)
            out["decisions"] = [
                {k: v for k, v in dd.items() if k != "ts"}
                for dd in st.get("decisions", [])][-6:]

            ratio = out["hot_p99_during_ms"] / \
                max(out["hot_p99_before_ms"], 0.01)
            out["value"] = round(ratio, 3)
            # generous VM-noise gate: transitions must not blow the hot
            # plane's tail out by an order of magnitude
            out["p99_gate_ok"] = \
                out["hot_p99_during_ms"] <= max(
                    5 * out["hot_p99_before_ms"], 100.0)
            if not out["p99_gate_ok"]:
                raise SystemExit(
                    f"hot-volume p99 regressed while transitions ran: "
                    f"{out['hot_p99_before_ms']}ms -> "
                    f"{out['hot_p99_during_ms']}ms")
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    return out


def lint_bench() -> dict:
    """--lint mode (ISSUE 8): time the full-tree house-rules analyzer
    pass. The contract is < 30 s on the 2-core CI VM — cheap enough
    that every PR runs it as a tier-1 test; the bench records the
    actual cost (best of 3) and the per-check finding counts at HEAD.
    """
    from seaweedfs_tpu.analysis import check_names, run

    times = []
    findings = []
    for _ in range(3):
        t0 = time.perf_counter()
        findings = run()
        times.append(time.perf_counter() - t0)
    best = min(times)
    per_check = {}
    for f in findings:
        per_check[f.check] = per_check.get(f.check, 0) + 1
    out = {
        "metric": "lint_full_tree_seconds",
        "value": round(best, 3),
        "unit": "s",
        "budget_s": 30.0,
        "within_budget": best < 30.0,
        "runs": [round(t, 3) for t in times],
        "checks": sorted(check_names()),
        "findings_total": len(findings),
        "findings_per_check": per_check,
    }
    if not out["within_budget"]:
        raise SystemExit(
            f"lint pass took {best:.1f}s — over the 30s tier-1 budget")
    return out


def qos_isolation_sweep() -> dict:
    """--qos mode: multi-tenant latency isolation on a real subprocess
    cluster (ISSUE 19 acceptance).

    One master + one volume server; a VICTIM tenant reads one hot
    needle at a paced, in-budget rate while an AGGRESSOR tenant floods
    the same server from keep-alive connections. Four scenarios:

      solo            qos off, victim alone — the latency floor
      contended_off   qos off, aggressor flooding — the damage
      contended_on    -qos -qos.requestRate: the aggressor is shed at
                      its per-tenant budget, the victim never is
      background_on   qos on + -scrub.intervalSeconds forcing scrub
                      passes (the _internal tenant) under the victim

    Gates (the JSON carries both): with qos ON the victim's p99 stays
    within BENCH_QOS_MAX_INFLATION (3x) of solo, the victim sheds
    ZERO requests, and the aggressor sheds > 0 (proof admission
    actually engaged — a no-op pass would also have zero victim shed).
    """
    import http.client
    import subprocess
    import tempfile
    import threading
    import urllib.request

    seconds = float(os.environ.get("BENCH_QOS_SECONDS", "3.0"))
    victim_rps = float(os.environ.get("BENCH_QOS_VICTIM_RPS", "60"))
    tenant_rate = float(os.environ.get("BENCH_QOS_TENANT_RATE", "150"))
    aggressors = int(os.environ.get("BENCH_QOS_AGGRESSORS", "8"))
    max_inflation = float(os.environ.get("BENCH_QOS_MAX_INFLATION",
                                         "3.0"))

    def pct(samples, q):
        if not samples:
            return 0.0
        s = sorted(samples)
        return s[min(len(s) - 1, int(q * len(s)))]

    def boot(d, tag, *extra):
        mport, vport = _free_port(), _free_port()
        procs = [_spawn_server("master", "-port", str(mport),
                               "-mdir", os.path.join(d, f"m-{tag}"),
                               "-volumeSizeLimitMB", "64",
                               "-pulseSeconds", "0.3")]
        _wait_http(f"http://127.0.0.1:{mport}/dir/status")
        procs.append(_spawn_server(
            "volume", "-port", str(vport),
            "-dir", os.path.join(d, f"v-{tag}"), "-max", "8",
            "-mserver", f"127.0.0.1:{mport}",
            "-pulseSeconds", "0.3", *extra))
        _wait_http(f"http://127.0.0.1:{vport}/status")
        time.sleep(0.7)   # first heartbeat registers the node
        with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/dir/assign") as r:
            a = json.load(r)
        body = os.urandom(4096)
        bnd = "b0und"
        payload = ((f"--{bnd}\r\nContent-Disposition: form-data;"
                    f' name="file"; filename="x"\r\n\r\n').encode() +
                   body + f"\r\n--{bnd}--\r\n".encode())
        rq = urllib.request.Request(
            f"http://{a['url']}/{a['fid']}", data=payload,
            method="POST",
            headers={"Content-Type":
                     f"multipart/form-data; boundary={bnd}",
                     "X-Seaweed-Tenant": "victim"})
        with urllib.request.urlopen(rq):
            pass
        return procs, vport, a["fid"]

    def victim_pace(port, fid, out):
        """Paced keep-alive reads, per-request latency + shed count."""
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        period = 1.0 / victim_rps
        next_t = time.perf_counter()
        deadline = next_t + seconds
        while time.perf_counter() < deadline:
            delay = next_t - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            next_t += period
            t0 = time.perf_counter()
            try:
                conn.request("GET", f"/{fid}",
                             headers={"X-Seaweed-Tenant": "victim"})
                resp = conn.getresponse()
                resp.read()
                status = resp.status
            except OSError:
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=10)
                status = 599
            out["lat"].append(time.perf_counter() - t0)
            if status != 200:
                out["shed"] += 1
        conn.close()

    def aggressor_flood(port, fid, stop, out, lock):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        ok = shed = 0
        while not stop.is_set():
            try:
                conn.request("GET", f"/{fid}",
                             headers={"X-Seaweed-Tenant": "hog"})
                resp = conn.getresponse()
                resp.read()
                if resp.status == 200:
                    ok += 1
                else:
                    shed += 1
            except OSError:
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=10)
        conn.close()
        with lock:
            out["ok"] += ok
            out["shed"] += shed

    def scenario(d, tag, flood, *extra):
        procs, vport, fid = boot(d, tag, *extra)
        victim = {"lat": [], "shed": 0}
        hogs = {"ok": 0, "shed": 0}
        lock = threading.Lock()
        stop = threading.Event()
        threads = []
        try:
            if flood:
                threads = [threading.Thread(
                    target=aggressor_flood,
                    args=(vport, fid, stop, hogs, lock), daemon=True)
                    for _ in range(aggressors)]
                for t in threads:
                    t.start()
                time.sleep(0.3)    # flood established before pacing
            victim_pace(vport, fid, victim)
            stop.set()
            for t in threads:
                t.join(timeout=10)
            status = {}
            if extra and "-qos" in extra:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{vport}/qos/status") as r:
                    status = json.load(r)
            return {
                "victim_p50_ms":
                    round(pct(victim["lat"], 0.50) * 1000, 2),
                "victim_p99_ms":
                    round(pct(victim["lat"], 0.99) * 1000, 2),
                "victim_requests": len(victim["lat"]),
                "victim_shed": victim["shed"],
                "aggressor_ok": hogs["ok"],
                "aggressor_shed": hogs["shed"],
                "qos_status": {
                    t: {"admitted": s["admitted"], "shed": s["shed"]}
                    for t, s in
                    status.get("tenants", {}).items()},
            }
        finally:
            stop.set()
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()

    qos_args = ("-qos", "-qos.requestRate", str(tenant_rate))
    with tempfile.TemporaryDirectory() as d:
        solo = scenario(d, "solo", False)
        off = scenario(d, "off", True)
        on = scenario(d, "on", True, *qos_args)
        bg = scenario(d, "bg", False, *qos_args,
                      "-scrub.intervalSeconds", "0.5")

    # the isolation gate: noise floor 2ms so a loopback solo p99 of
    # 0.3ms doesn't demand sub-millisecond contended latency
    floor_ms = max(solo["victim_p99_ms"], 2.0)
    inflation = on["victim_p99_ms"] / floor_ms
    line = {
        "metric": "qos_tenant_isolation",
        "unit": "x victim p99 inflation (qos on vs solo)",
        "value": round(inflation, 3),
        "seconds": seconds,
        "victim_rps": victim_rps,
        "tenant_request_rate": tenant_rate,
        "aggressor_conns": aggressors,
        "solo": solo,
        "contended_off": off,
        "contended_on": on,
        "background_on": bg,
        "gates": {
            "max_inflation": max_inflation,
            "victim_p99_within_bound": inflation <= max_inflation,
            "victim_zero_shed": on["victim_shed"] == 0
            and bg["victim_shed"] == 0,
            "aggressor_was_shed": on["aggressor_shed"] > 0,
        },
    }
    g = line["gates"]
    if not (g["victim_p99_within_bound"] and g["victim_zero_shed"]
            and g["aggressor_was_shed"]):
        raise SystemExit(f"qos isolation gate failed: {g}")
    return line


def main() -> None:
    if "--qos" in sys.argv:
        # qos mode is host-pipeline only: tenant latency isolation on
        # real subprocess servers, not the kernel headline
        line = qos_isolation_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_QOS.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--lifecycle" in sys.argv:
        line = lifecycle_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_LIFECYCLE.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--lint" in sys.argv:
        line = lint_bench()
        with open(os.path.join(REPO_ROOT, "BENCH_LINT.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--meta" in sys.argv:
        # meta mode is host-pipeline only: metadata-plane lookup +
        # listing throughput against subprocess servers, not the
        # kernel headline
        line = meta_plane_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_META.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--serve" in sys.argv:
        # serve mode is host-pipeline only: threaded vs async serving
        # core on real subprocess servers, not the kernel headline
        line = serve_async_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_SERVE.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--chaos" in sys.argv:
        line = chaos_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_CHAOS.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--ingest" in sys.argv:
        # ingest mode is host-pipeline only: filer write-path
        # throughput, not the kernel headline
        line = ingest_pipeline_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_INGEST.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--degraded" in sys.argv:
        # degraded mode is host-pipeline only: serving-path decode
        # throughput, not the kernel headline
        line = degraded_read_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_DEGRADED.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--scrub" in sys.argv:
        # scrub mode is host-pipeline only: verify throughput of the
        # integrity scanner, not the kernel headline
        print(json.dumps(scrub_verify_sweep()), flush=True)
        return
    if "--mesh" in sys.argv:
        # mesh mode forces a virtual 8-device CPU platform, so it must
        # own the process: unified pod-scale scheduler vs per-device
        # fleet schedulers (host-pipeline, not the kernel headline)
        line = mesh_batch_sweep()
        with open(os.path.join(REPO_ROOT, "BENCH_MESH.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--trace-cluster" in sys.argv:
        # cluster-trace mode: enabled-path overhead of cross-hop
        # tracing on the data plane (host-pipeline only)
        line = cluster_trace_bench()
        with open(os.path.join(REPO_ROOT, "BENCH_TRACE.json"),
                  "w") as f:
            json.dump(line, f, indent=1)
        print(json.dumps(line), flush=True)
        return
    if "--trace" in sys.argv:
        # trace mode is host-pipeline only (no TPU needed): stage
        # attribution of the fleet scheduler, not the kernel headline
        i = sys.argv.index("--trace")
        out_path = sys.argv[i + 1] if len(sys.argv) > i + 1 and \
            not sys.argv[i + 1].startswith("-") else "bench_trace.json"
        print(json.dumps(fleet_trace_bench(out_path)), flush=True)
        return
    device = require_accelerator()
    backend = _cpu_backend()
    enc_m, reb_m = _matrices()
    cpu_enc = cpu_phase_gbps(enc_m, backend)
    cpu_reb = cpu_phase_gbps(reb_m, backend)
    tpu_enc = tpu_phase_gbps(enc_m)
    tpu_reb = tpu_phase_gbps(reb_m)
    tpu = _combined(tpu_enc, tpu_reb)
    cpu = _combined(cpu_enc, cpu_reb)
    print(json.dumps({
        "metric": "ec_encode_rebuild_gbps",
        "value": round(tpu, 3),
        "unit": "GB/s",
        "device": device,
        "vs_baseline": round(tpu / cpu, 3),
        "encode_gbps": round(tpu_enc, 3),
        "rebuild_gbps": round(tpu_reb, 3),
        "baseline_backend": backend,
        "baseline_gbps": round(cpu, 3),
        "baseline_encode_gbps": round(cpu_enc, 3),
        "baseline_rebuild_gbps": round(cpu_reb, 3),
    }), flush=True)
    # second line: the cross-volume fleet scheduler sweep (1/8/64
    # volumes, fused vs serial). A failed phase fails the run.
    print(json.dumps(fleet_batch_sweep()), flush=True)


if __name__ == "__main__":
    sys.exit(main())
