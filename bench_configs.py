"""BASELINE.md config-matrix measurements (configs 1-8).

Usage: python bench_configs.py [1|2|3|4|5|6|7|8|all]

Each config prints one JSON line; results are recorded in BASELINE.md.
Config definitions come from BASELINE.json / BASELINE.md:

1. Single 1GB .dat, RS(10,4) ec.encode on CPU (native AVX2 backend —
   the klauspost/reedsolomon stand-in) through the repo's own
   write_ec_files path (file IO included).
2. Sustained on-device jax encode (bench.py methodology: chained
   full-parity dependence, >VMEM working set) + the same 1GB
   write_ec_files end-to-end with backend=jax (includes host IO and
   the host<->device transfers). One process: the chip has one owner.
3. Rebuild with 2 missing shards: host rebuild_ec_files on the 1GB
   volume (native), plus the on-device reconstruct kernel rate.
4. 8-way sharded encode on a virtual CPU mesh (correctness +
   scaling-shape check; per-chip GB/s comes from config 2 — multi-chip
   hardware is not reachable from this image).
5. Mixed workload: p99 needle-read latency while an ec.encode runs on
   the same volume server, with the -compactionMBps throttler engaged
   vs unthrottled vs idle.
"""

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

GB = 1 << 30
DAT_SIZE = 1 * GB


def _make_dat(path: str, size: int = DAT_SIZE) -> None:
    """Synthetic .dat: 8B superblock + pseudo-random bytes (cheap:
    tiled PCG block, content irrelevant to throughput)."""
    rng = np.random.default_rng(7)
    block = rng.integers(0, 256, 16 << 20, dtype=np.uint8).tobytes()
    with open(path, "wb") as f:
        f.write(b"\x03\x00\x00\x00\x00\x00\x00\x00")
        written = 8
        while written < size:
            n = min(len(block), size - written)
            f.write(block[:n])
            written += n


def _encode_once(base: str, backend: str) -> float:
    from seaweedfs_tpu.ec import encoder
    t0 = time.perf_counter()
    encoder.write_ec_files(base, backend=backend)
    return time.perf_counter() - t0


def config1() -> dict:
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "1")
        _make_dat(base + ".dat")
        dt = _encode_once(base, "native")
        gbps = DAT_SIZE / GB / dt
    return {"config": 1, "metric": "ec_encode_cpu_native_1gb",
            "wall_s": round(dt, 2), "value": round(gbps, 3),
            "unit": "GB/s"}


def config2() -> dict:
    # end-to-end 1GB through write_ec_files with the jax backend
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "1")
        _make_dat(base + ".dat")
        dt = _encode_once(base, "jax")
        e2e_gbps = DAT_SIZE / GB / dt
    # sustained on-device rate: bench.py's chained kernel loop, run IN
    # this process — the chip belongs to one process at a time, and
    # this one already holds it (a bench.py child would fail or hang)
    import bench
    device = bench.require_accelerator()
    enc_m, _ = bench._matrices()
    return {"config": 2, "metric": "ec_encode_jax_1gb",
            "device": device,
            "device_gbps": round(bench.tpu_phase_gbps(enc_m), 3),
            "e2e_wall_s": round(dt, 2),
            "e2e_gbps": round(e2e_gbps, 3),
            "note": "e2e includes disk and host<->device transfers"}


def config3() -> dict:
    from seaweedfs_tpu.ec import encoder
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "1")
        _make_dat(base + ".dat")
        encoder.write_ec_files(base, backend="native")
        # drop 2 shards (one data, one parity) and rebuild
        for sid in (3, 11):
            os.remove(encoder.shard_file_name(base, sid))
        t0 = time.perf_counter()
        rebuilt = encoder.rebuild_ec_files(base, backend="native")
        dt = time.perf_counter() - t0
        assert sorted(rebuilt) == [3, 11]
        shard_bytes = os.path.getsize(encoder.shard_file_name(base, 0))
    return {"config": 3, "metric": "ec_rebuild_2shards_cpu_native",
            "wall_s": round(dt, 2),
            "value": round(2 * shard_bytes / GB / dt, 3),
            "unit": "GB/s rebuilt"}


def config4() -> dict:
    # virtual 8-device CPU mesh: shard the lane dimension, validate the
    # sharded program and report its (CPU-bound) rate for the record
    from seaweedfs_tpu.util import cpu_mesh
    cpu_mesh.force_cpu_platform(8)
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from seaweedfs_tpu.ops import rs_kernel
    from seaweedfs_tpu.ops.rs_code import coding_matrix, DATA_SHARDS
    devs = np.array(jax.devices("cpu")[:8])
    mesh = Mesh(devs, ("shard",))
    m2 = rs_kernel.m2_bits(np.asarray(coding_matrix())[DATA_SHARDS:])
    lanes = 8 << 20
    data = np.random.default_rng(0).integers(
        0, 256, (DATA_SHARDS, lanes), dtype=np.uint8)
    sharding = NamedSharding(mesh, P(None, "shard"))
    x = jax.device_put(data, sharding)

    @jax.jit
    def enc(d):
        return rs_kernel.gf_linear(m2, d)

    enc(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = enc(x)
        out.block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    # correctness vs numpy
    from seaweedfs_tpu.ops.rs_code import ReedSolomon
    ref = ReedSolomon(backend="numpy").encode(data)
    assert np.array_equal(np.asarray(out), ref)
    return {"config": 4, "metric": "ec_encode_8way_cpu_mesh",
            "devices": 8, "value": round(
                DATA_SHARDS * lanes / GB / dt, 3),
            "unit": "GB/s (virtual CPU mesh; shape/collective check, "
                    "not TPU perf)"}


def config5() -> dict:
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.ec import store_ec

    def run_case(throttle_mbps):
        with tempfile.TemporaryDirectory() as d:
            store = Store([d])
            store.add_volume(1)
            v = store.find_volume(1)
            blob = os.urandom(64 << 10)
            for i in range(1, 1501):
                v.write_needle(Needle(id=i, cookie=7, data=blob))
            lat = []
            stop = threading.Event()

            def reader():
                i = 1
                while not stop.is_set():
                    t0 = time.perf_counter()
                    v.read_needle(Needle(id=(i % 1500) + 1, cookie=7))
                    lat.append(time.perf_counter() - t0)
                    i += 1
                    time.sleep(0.002)

            th = threading.Thread(target=reader, daemon=True)
            th.start()
            if throttle_mbps is not None:
                from seaweedfs_tpu.util.throttler import Throttler
                throttler = Throttler(throttle_mbps)
                # encode with throttled chunk pacing: emulate the
                # server path's -compactionMBps on shard generation
                from seaweedfs_tpu.ec import encoder as enc_mod
                orig = enc_mod._read_padded

                def slow_read(f, offset, length):
                    throttler.maybe_slowdown(length)
                    return orig(f, offset, length)
                enc_mod._read_padded = slow_read
                try:
                    v.read_only = True
                    store_ec.generate_ec_shards(store, 1, backend="native")
                finally:
                    enc_mod._read_padded = orig
            time.sleep(0.3)
            stop.set()
            th.join(timeout=5)
            store.close()
            lat.sort()
            return lat[int(len(lat) * 0.99)] * 1000 if lat else 0.0

    idle = run_case(None)
    unthrottled = run_case(0)       # 0 = throttler disabled
    throttled = run_case(200)       # 200 MB/s cap
    return {"config": 5, "metric": "read_p99_during_ec_encode_ms",
            "idle_p99_ms": round(idle, 2),
            "encode_unthrottled_p99_ms": round(unthrottled, 2),
            "encode_throttled_200mbps_p99_ms": round(throttled, 2)}


def _phase_stats(st, seconds: float) -> dict:
    ms = sorted(st.latencies_ms)
    return {
        "req_per_s": round(st.completed / seconds, 1) if seconds else 0.0,
        "p50_ms": round(st.percentile(ms, 50), 2),
        "p99_ms": round(st.percentile(ms, 99), 2),
        "failed": st.failed,
    }


def config6() -> dict:
    """Write-path A/B: round-1-style synchronous per-write commits vs
    the round-2 group-commit worker (storage/volume.py
    _GroupCommitWriter), measured with the in-binary load generator at
    the reference's shape (c=16, 1KB; reference weed benchmark
    README.md:493-503 = 15,708 req/s on 2012 hardware). Proves the
    worker earns its complexity (round-2 verdict item 7)."""
    import os as _os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import io
    import tempfile

    from seaweedfs_tpu.command.benchmark import run_benchmark_programmatic
    from seaweedfs_tpu.storage import volume as volume_mod
    from tests.cluster_util import Cluster

    n = int(_os.environ.get("BENCH6_N", 100_000))
    results = {}
    for mode, async_write in (("sync_per_write", False),
                              ("group_commit", True)):
        orig = volume_mod.Volume.__init__

        def patched(self, *a, **kw):
            kw["async_write"] = async_write
            orig(self, *a, **kw)

        volume_mod.Volume.__init__ = patched
        c = None
        try:
            import pathlib
            tmp = pathlib.Path(tempfile.mkdtemp(prefix=f"bench6-{mode}-"))
            c = Cluster(tmp, n_volume_servers=1)
            r = run_benchmark_programmatic(
                c.master.url, n=n, concurrency=16, size=1024,
                do_read=False, out=io.StringIO())
            results[mode] = _phase_stats(r["write"], r["write_seconds"])
        finally:
            volume_mod.Volume.__init__ = orig
            if c is not None:
                c.stop()
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)
    results["config"] = 6
    results["n"] = n
    results["speedup"] = round(
        results["group_commit"]["req_per_s"] /
        max(results["sync_per_write"]["req_per_s"], 0.001), 2)
    return results


def config7() -> dict:
    """Small-file data plane, round-4 shape (BASELINE.md config 6b):
    write + random-read through the public path (HTTP /dir/assign +
    pooled volume-server HTTP), c=16, 1KB, in-process cluster."""
    import io
    import pathlib

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from seaweedfs_tpu.command.benchmark import run_benchmark_programmatic
    from tests.cluster_util import Cluster

    n = int(os.environ.get("BENCH7_N", 30_000))
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench7-"))
    c = Cluster(tmp, n_volume_servers=1)
    try:
        r = run_benchmark_programmatic(
            c.master.url, n=n, concurrency=16, size=1024,
            do_read=True, out=io.StringIO())
    finally:
        c.stop()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    out = {"config": 7, "n": n}
    for phase in ("write", "read"):
        out[phase] = _phase_stats(r[phase], r[f"{phase}_seconds"])
    return out


def _drive(n: int, concurrency: int, op) -> dict:
    """Run op(i) from `concurrency` threads, n times total; returns
    req/s + latency percentiles (the config-7 stats shape)."""
    import threading
    import time as _t
    lat = []
    lock = threading.Lock()
    counter = iter(range(n))
    failed = [0]

    def worker():
        while True:
            with lock:
                i = next(counter, None)
            if i is None:
                return
            t0 = _t.monotonic()
            try:
                op(i)
                dt = (_t.monotonic() - t0) * 1e3
                with lock:
                    lat.append(dt)
            except Exception:
                with lock:
                    failed[0] += 1

    t0 = _t.monotonic()
    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    secs = _t.monotonic() - t0
    lat.sort()
    pct = lambda p: round(lat[min(len(lat) - 1, int(p * len(lat)))], 2) \
        if lat else 0.0
    return {"req_per_s": round(len(lat) / secs, 1), "p50_ms": pct(0.5),
            "p99_ms": pct(0.99), "failed": failed[0]}


class _SigV4:
    """Pooled-transport S3 bench client: signature math rides the
    repo's own util.aws_auth.sigv4_headers (the same canonical-request
    chain the gateway verifies); only the send path is the pooled
    keep-alive client."""

    def __init__(self, endpoint, access, secret, region="us-east-1"):
        self.endpoint, self.access = endpoint, access
        self.secret, self.region = secret, region

    def request(self, method: str, path: str, payload: bytes = b""):
        from seaweedfs_tpu.util import http_client
        from seaweedfs_tpu.util.aws_auth import sigv4_headers
        headers = sigv4_headers(method, self.endpoint, path, [], {},
                                payload, self.access, self.secret,
                                self.region, "s3")
        headers.pop("host", None)  # the pooled client sets Host itself
        r = http_client.request(
            method, f"{self.endpoint}{path}", body=payload or None,
            headers=headers)
        if r.status >= 300:
            raise RuntimeError(f"s3 {method} {path}: {r.status}")
        return r


def config8() -> dict:
    """Filer + S3 data planes (VERDICT r4 #2): same 1KB/c=16 shape as
    config 7 but through filer POST/GET /path (auto-chunking,
    filer_server_handlers_write_autochunk.go) and s3 PUT/GET (SigV4,
    s3api/auth_signature_v4.go)."""
    import pathlib

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from seaweedfs_tpu.s3api.auth import (ACTION_ADMIN, Credential, Iam,
                                          Identity)
    from seaweedfs_tpu.s3api.server import S3ApiServer
    from seaweedfs_tpu.util import http_client
    from tests.cluster_util import Cluster, free_port_pair

    n = int(os.environ.get("BENCH8_N", 15_000))  # BASELINE.md runs use 15k
    c16 = 16
    payload = bytes(i * 31 % 256 for i in range(1024))
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench8-"))
    cluster = Cluster(tmp, n_volume_servers=1, with_filer=True)
    s3srv = S3ApiServer(
        filer_url=cluster.filer.url, port=free_port_pair(),
        iam=Iam([Identity(name="bench",
                          credentials=[Credential("benchak", "benchsk")],
                          actions=[ACTION_ADMIN])]))
    s3srv.start()
    out = {"config": 8, "n": n}
    try:
        filer = cluster.filer.url
        out["filer_write"] = _drive(
            n, c16, lambda i: http_client.request(
                "POST", f"{filer}/bench/f{i}", body=payload))
        out["filer_read"] = _drive(
            n, c16, lambda i: http_client.request(
                "GET", f"{filer}/bench/f{i}"))
        s3c = _SigV4(s3srv.url, "benchak", "benchsk")
        s3c.request("PUT", "/benchbkt")
        out["s3_write"] = _drive(
            n, c16, lambda i: s3c.request("PUT", f"/benchbkt/o{i}",
                                          payload))
        out["s3_read"] = _drive(
            n, c16, lambda i: s3c.request("GET", f"/benchbkt/o{i}"))
    finally:
        s3srv.stop()
        cluster.stop()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    configs = {"1": config1, "2": config2, "3": config3, "4": config4,
               "5": config5, "6": config6, "7": config7, "8": config8}
    if which == "all":
        # each config in its own subprocess: config2 initializes the
        # TPU backend in-process, which would make config4's
        # force_cpu_platform impossible in the same interpreter
        import subprocess
        for n in configs:
            try:
                r = subprocess.run([sys.executable, __file__, n],
                                   capture_output=True, text=True,
                                   timeout=1800)
            except subprocess.TimeoutExpired:
                print(json.dumps({"config": int(n),
                                  "error": "timed out after 1800s"}),
                      flush=True)
                continue
            out = r.stdout.strip()
            if r.returncode != 0 or not out:
                print(json.dumps({"config": int(n), "error":
                                  r.stderr.strip()[-300:]}), flush=True)
            else:
                print(out.splitlines()[-1], flush=True)
        return
    print(json.dumps(configs[which]()), flush=True)


if __name__ == "__main__":
    main()
