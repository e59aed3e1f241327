"""Coarse perf-regression gates (VERDICT r4 #7).

Thresholds are deliberately generous — a 4-8x margin below the
measured numbers in BASELINE.md — so CI catches order-of-magnitude
regressions (a dropped TCP_NODELAY re-introducing the 40 ms Nagle
stall, the EC kernel silently falling back to the numpy path, an
accidental conn-per-request client) without flaking on VM load, which
moves the real numbers ±20%.
"""

import io
import os
import time

import numpy as np
import pytest

from tests.cluster_util import Cluster


def test_data_plane_floor(tmp_path, capsys):
    """In-process config-7 shape, small n: every request of the write
    and the read plane completes, over sockets with Nagle off.

    The regression class this exists to catch is a dropped TCP_NODELAY
    (the Nagle-stalled plane: ~360 req/s both ways, against ~3,600
    write / ~12,000 read, BASELINE.md round 5). That is asserted as
    what repeats exactly on any machine: the option read back from the
    run's own client sockets and from the sockets the volume server
    accepted for them. Connection reuse, the other half of the class,
    is test_pooled_client_reuses_connections's. The two rates are
    printed, not asserted: a rate belongs to a benchmark cell
    (`weed-bench.small-io`, PERF.md section 7), on a machine that runs
    nothing else.
    """
    import socket

    from seaweedfs_tpu.command.benchmark import run_benchmark_programmatic
    from seaweedfs_tpu.util import http_client
    n = 2500
    c = Cluster(tmp_path, n_volume_servers=1)
    try:
        r = run_benchmark_programmatic(c.master.url, n=n,
                                       concurrency=8, size=1024,
                                       do_read=True, out=io.StringIO())
        vs = c.volume_servers[0]
        with http_client._pool_lock:
            dialed = [conn.sock for conn in http_client._pool[vs.url]]
        with vs._http_server._conns_lock:
            accepted = list(vs._http_server._conns)
        nodelay = [s.getsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY)
                   for s in dialed + accepted]
    finally:
        c.stop()
    assert r["write"].failed == 0 and r["read"].failed == 0
    assert r["write"].completed == n and r["read"].completed == n
    # the load threads' keep-alive connections are still up, both ends
    assert dialed and len(accepted) >= len(dialed)
    assert all(nodelay), f"Nagle is on: {nodelay}"
    with capsys.disabled():
        print(f"\ndata plane, not asserted: "
              f"{n / r['write_seconds']:.0f} write req/s, "
              f"{n / r['read_seconds']:.0f} read req/s")


def test_ec_kernel_floor(monkeypatch):
    """The host EC codec is the native library, not a quiet stand-in.

    The regression class this gate exists for is a silent fall from
    the C++ kernel to the numpy path (an order of magnitude slower).
    Since PR 22 a backend asked for by name is used or raises, so that
    is asserted as counts (ROADMAP C8): this host builds the library,
    `backend="native"` reaches `rs_native.apply_matrix` exactly once
    per encode, `backend="auto"` resolves to it too, and the bytes
    equal numpy's. The wall-clock floor stays only as a loose guard
    against a pure-Python regression (0.10-0.13 GB/s measured for the
    native kernel under the six-worker suite on a loaded machine, 1.2+
    idle). Device rates belong to the benchmark (`bench.py`), which
    fails without a chip; none is asserted here.
    """
    from seaweedfs_tpu.native import rs_native
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_code import DATA_SHARDS, ReedSolomon

    assert rs_native.available(), rs_native.load_error()
    calls = []
    real = rs_native.apply_matrix

    def counted(matrix, shards):
        calls.append(shards.shape)
        return real(matrix, shards)

    monkeypatch.setattr(rs_native, "apply_matrix", counted)
    data = np.random.default_rng(3).integers(
        0, 256, (DATA_SHARDS, 4 << 20), dtype=np.uint8)
    rs = ReedSolomon(backend="native")
    rs.encode(data[:, : 1 << 16])  # warm
    t0 = time.perf_counter()
    parity = rs.encode(data)
    dt = time.perf_counter() - t0
    assert calls == [(DATA_SHARDS, 1 << 16), (DATA_SHARDS, 4 << 20)]
    ReedSolomon(backend="auto").encode(data[:, : 1 << 16])
    assert len(calls) == 3, "backend='auto' left the native library"
    sample = slice(0, 1 << 14)
    assert np.array_equal(
        parity[:, sample],
        gf256.gf_linear_numpy(rs.matrix[DATA_SHARDS:], data[:, sample]))
    gbps = data.nbytes / (1 << 30) / dt
    assert gbps >= 0.02, f"native EC kernel regressed: {gbps:.3f} GB/s"


def _fleet_stage_threads(run):
    """Run `run()` with the tracer on; returns {stage span name:
    [thread name of each span]} for the fleet.* stage spans."""
    from seaweedfs_tpu.stats import trace
    trace.enable()
    trace.clear()
    try:
        run()
        events = trace.chrome_trace()["traceEvents"]
    finally:
        trace.disable()
        trace.clear()
    names = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    stages = {}
    for e in events:
        if e["ph"] == "X" and e["name"].startswith("fleet."):
            stages.setdefault(e["name"], []).append(
                names.get(e["tid"], "?"))
    return stages


def test_fleet_batched_encode_floor(tmp_path):
    """Cross-volume fleet encode vs serial per-volume encode (8×4MB,
    native backend): the fleet scheduler keeps its overlap.

    The regression class: the reader pool gone synchronous, writer
    lanes collapsed to one serialized thread, the encode pool bypassed.
    That is a fact about WHERE each stage runs, so it is asserted as
    counts from the scheduler's own stage spans (ROADMAP C8: rates
    belong to the benchmark, counts to tier-1): every read on a
    reader-pool thread, every host RS compute on an encode-pool
    thread, the retire on its own thread, and the eight volumes' writes
    on ALL the writer lanes (volume tag % lanes is deterministic) —
    none of it on the calling thread. The fused-vs-serial wall-clock
    ratio this test used to gate (1.5x "at >=8 cores", never met on
    eight loaded cores: 0.22-1.34x measured under the six-worker suite)
    is `bench.py`'s fleet sweep; here it is printed, not asserted.
    Shard bytes stay identical to the serial path.
    """
    import threading

    from seaweedfs_tpu.ec import encoder as enc
    from seaweedfs_tpu.ec import fleet
    from seaweedfs_tpu.native import rs_native

    backend = "native" if rs_native.available() else "numpy"
    rng = np.random.default_rng(11)
    block = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    vol = 4 << 20
    serial_bases, fleet_bases = [], []
    for v in range(8):
        base = str(tmp_path / f"f{v}")
        with open(base + ".dat", "wb") as f:
            for _ in range(vol // len(block)):
                f.write(block)
        fleet_bases.append(base)
        twin = str(tmp_path / f"s{v}")
        os.link(base + ".dat", twin + ".dat")
        serial_bases.append(twin)

    t0 = time.perf_counter()
    for base in serial_bases:
        enc.write_ec_files(base, backend=backend)
    serial_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    stages = _fleet_stage_threads(
        lambda: fleet.fleet_write_ec_files(fleet_bases, backend=backend))
    fused_s = time.perf_counter() - t0
    print(f"fleet fused-vs-serial {serial_s / fused_s:.2f}x "
          f"(serial={serial_s:.3f}s fused={fused_s:.3f}s, tracer on; "
          "not a gate)")

    me = threading.current_thread().name
    # one 10MB-row span per 4MB volume: 8 reads, 8 RS computes
    assert len(stages["fleet.read"]) == 8
    assert all(t.startswith("fleet-read") for t in stages["fleet.read"]), \
        f"reads left the reader pool: {sorted(set(stages['fleet.read']))}"
    assert len(stages["fleet.rs"]) == 8
    assert all(t.startswith("fleet-encode") for t in stages["fleet.rs"]), \
        f"RS compute left the encode pool: {sorted(set(stages['fleet.rs']))}"
    assert set(stages["fleet.retire"]) == {"fleet-retire"}
    # the set-up span (creating the 14 output files) is the caller's;
    # every data/parity write belongs to a lane
    lane_writes = [t for t in stages["fleet.write"] if t != me]
    assert len(lane_writes) == 16      # 8 data-shard + 8 parity spans
    assert set(lane_writes) == {f"fleet-write-{i}"
                                for i in range(fleet.FLEET_WRITERS)}, \
        f"writer lanes collapsed: {sorted(set(lane_writes))}"
    assert stages["fleet.write"].count(me) == 1
    for stage in ("fleet.read", "fleet.rs", "fleet.retire"):
        assert me not in stages[stage], f"{stage} ran on the caller"
    for fb, sb in zip(fleet_bases, serial_bases):
        for sid in range(14):
            with open(enc.shard_file_name(fb, sid), "rb") as f1, \
                    open(enc.shard_file_name(sb, sid), "rb") as f2:
                assert f1.read() == f2.read(), (fb, sid)


def test_tracing_disabled_overhead(tmp_path):
    """Tracing must be zero-cost when off (ISSUE 2 tentpole contract).

    Two gates. Micro: the disabled span() fast path is one flag check
    returning a shared no-op — 200k calls must stay far under real
    span cost (generous 5 us/call ceiling vs ~0.1 us measured).
    Macro, as counts (ROADMAP C8 — the wall-clock ratio of two fleet
    encodes this used to gate swung past its 1.6x bound on a loaded
    machine with nothing changed): over an 8-volume fleet encode with
    the tracer present-but-disabled, (a) every stage interval gets the
    ONE shared no-op span — no Span object is allocated, nothing
    reaches the ring; and (b) the number of stage intervals stays
    per-chunk — the regression class is instrumentation gone
    accidentally per-row or per-byte, which multiplies this count by
    the rows (8 per volume here) or more."""
    from seaweedfs_tpu.ec import fleet
    from seaweedfs_tpu.native import rs_native
    from seaweedfs_tpu.stats import trace

    assert not trace.is_enabled()
    t0 = time.perf_counter()
    for _ in range(200_000):
        trace.span("hot", vid=1)
    per_call = (time.perf_counter() - t0) / 200_000
    assert per_call < 5e-6, \
        f"disabled span() costs {per_call * 1e6:.2f} us/call"
    assert trace.span("hot", vid=1) is trace.NOOP

    backend = "native" if rs_native.available() else "numpy"
    rng = np.random.default_rng(17)
    block = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    bases = []
    for v in range(8):
        base = str(tmp_path / f"i{v}")
        with open(base + ".dat", "wb") as f:
            for _ in range(8):
                f.write(block)
        bases.append(base)

    real_timer = fleet._StageTimer
    made = []

    class _CountingTimer(real_timer):
        __slots__ = ()

        def __init__(self, stage, *a, **kw):
            super().__init__(stage, *a, **kw)
            made.append((stage, self._span))

    trace.clear()
    fleet._StageTimer = _CountingTimer
    try:
        fleet.fleet_write_ec_files(bases, backend=backend)
    finally:
        fleet._StageTimer = real_timer
    assert made, "the fleet encode closed no stage interval at all"
    assert all(span is trace.NOOP for _, span in made), \
        "a disabled tracer still allocated spans"
    assert trace.spans() == [], "a disabled tracer recorded spans"
    # 8 volumes x 8MB, one 10MB row each: per volume one read, one RS
    # compute, a data and a parity write; one dispatch + retire per
    # fused batch (<= 8), one set-up write. Per-row instrumentation
    # would multiply the per-volume terms by 8.
    per_stage = {}
    for stage, _ in made:
        per_stage[stage] = per_stage.get(stage, 0) + 1
    assert per_stage.get("read") == 8, per_stage
    assert per_stage.get("rs") == 8, per_stage
    assert per_stage.get("write") == 17, per_stage
    assert 1 <= per_stage.get("dispatch", 0) <= 8, per_stage
    assert per_stage.get("retire") == per_stage.get("dispatch"), per_stage
    assert len(made) <= 8 + 8 + 17 + 8 + 8, per_stage


@pytest.mark.parametrize("path", ["apply_matrix", "fleet_jax"])
def test_phase_timers_allocate_no_span_when_tracing_is_off(path, tmp_path):
    """The dispatch layer's phase timers (ops/rs_kernel.py) and the
    scheduler's pack and wait timers (ec/fleet.py) on the jax backend:
    with the tracer off they observe their histograms — and allocate no
    Span: the process's span-id counter does not advance, the ring
    stays empty. So does the counter of where results landed: the
    scheduler lends every dispatch its buffer's last rows, a bare
    apply_matrix makes an array. A count, not a time (ROADMAP C8)."""
    from seaweedfs_tpu.ec import fleet
    from seaweedfs_tpu.ops import rs_kernel
    from seaweedfs_tpu.ops.rs_code import DATA_SHARDS, coding_matrix
    from seaweedfs_tpu.stats import trace
    from seaweedfs_tpu.stats.metrics import (
        FleetStageSecondsHistogram, FleetWaitSecondsHistogram,
        RsDispatchSecondsHistogram, RsResultBuffersCounter)

    assert not trace.is_enabled()
    trace.clear()
    rng = np.random.default_rng(19)
    place = RsDispatchSecondsHistogram.labels("place")
    pack = FleetStageSecondsHistogram.labels("pack")
    reader = FleetWaitSecondsHistogram.labels("reader")
    staging = FleetWaitSecondsHistogram.labels("staging")
    counted = (place.count, pack.count, reader.count, staging.count)
    lent, fresh = (RsResultBuffersCounter.labels(s) for s in ("lent", "fresh"))
    landed = (lent.value, fresh.value)
    first_id = trace.next_span_id()
    if path == "apply_matrix":
        data = rng.integers(0, 256, (2, DATA_SHARDS, 4096), dtype=np.uint8)
        rs_kernel.apply_matrix_async(
            np.asarray(coding_matrix())[DATA_SHARDS:], data).result()
        assert place.count == counted[0] + 1
        assert (lent.value, fresh.value) == (landed[0], landed[1] + 1)
    else:
        bases = []
        for v in range(2):
            base = str(tmp_path / f"o{v}")
            with open(base + ".dat", "wb") as f:
                f.write(rng.integers(0, 256, 3 * 2560 + 7,
                                     dtype=np.uint8).tobytes())
            bases.append(base)
        fleet.fleet_write_ec_files(bases, backend="jax", large_block=2048,
                                   small_block=256, chunk=2 * 2560)
        assert place.count > counted[0]
        assert pack.count > counted[1]
        assert reader.count == counted[2] + 8      # 4 one-row spans each
        assert staging.count == counted[3] + 4     # 2 spans a buffer
        assert (lent.value, fresh.value) == (landed[0] + 4, landed[1])
    assert trace.next_span_id() == first_id + 1, \
        "a disabled tracer still allocated spans"
    assert trace.spans() == []


def test_storage_engine_microbench(tmp_path):
    """Raw storage-engine floors: the engine measured 36 us/write and
    17 us/read in round 4; 500/250 us floors catch an accidental
    fsync-per-write or per-needle reopen without flaking."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)])
    store.add_volume(1)
    v = store.find_volume(1)
    blob = bytes(range(256)) * 4
    n = 2000
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        v.write_needle(Needle(id=i, cookie=9, data=blob))
    write_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        v.read_needle(Needle(id=i, cookie=9))
    read_us = (time.perf_counter() - t0) / n * 1e6
    store.close()
    assert write_us <= 500, f"engine write {write_us:.0f} us/needle"
    assert read_us <= 250, f"engine read {read_us:.0f} us/needle"


def test_pooled_client_reuses_connections(tmp_path):
    """The data-plane client must NOT open a connection per request —
    the conn-per-request regression class produced 1 s SYN-retransmit
    p99 tails on three planes (BASELINE.md round 5)."""
    import socket

    from seaweedfs_tpu.util import http_client
    c = Cluster(tmp_path, n_volume_servers=1)
    connects = []
    orig = socket.create_connection

    def counting(addr, *a, **kw):
        connects.append(addr)
        return orig(addr, *a, **kw)

    socket.create_connection = counting
    try:
        fid = None
        from seaweedfs_tpu.operation import operations
        fid = operations.upload(c.master.url, b"x" * 100)
        before = len(connects)
        for _ in range(20):
            operations.upload(c.master.url, b"x" * 100)
            url = operations.lookup(
                c.master.url, int(fid.split(",")[0]))[0]
            r = http_client.request("GET", f"{url}/{fid}")
            assert r.status == 200
        # 60 requests (20 uploads x2 + 20 gets) over warm pools: a
        # handful of new conns is fine (pool growth), one per request
        # is the regression
        assert len(connects) - before <= 10, \
            f"{len(connects) - before} new connections for 60 requests"
    finally:
        socket.create_connection = orig
        c.stop()


def test_cache_disabled_overhead(tmp_path):
    """The tiered read cache must be zero-cost while disabled (ISSUE 4
    contract, the scrub/tracing-disabled twin for the read subsystem).

    Three gates. Construction: a volume server built without
    -cache.sizeMB holds NO cache object at all — the read path's
    cache branch is a None check, never a lookup. Threads: even a
    constructed TieredReadCache spawns none (it is pure data
    structures). Engine: EC needle reads with cache=None hold a
    generous per-read ceiling — the disabled path must not have grown
    a hashing/locking tax."""
    import threading

    from seaweedfs_tpu.cache import TieredReadCache
    from seaweedfs_tpu.ec import encoder, store_ec
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    def cache_threads():
        return [t.name for t in threading.enumerate()
                if "cache" in t.name.lower()]

    d = tmp_path / "vs"
    d.mkdir()
    vs = VolumeServer(master_url="127.0.0.1:1", directories=[str(d)])
    assert vs.read_cache is None, \
        "default-config server must not construct a read cache"
    vs.store.close()

    c = TieredReadCache(64 << 20)     # constructed but unwired
    assert cache_threads() == [], \
        "constructing the read cache must not spawn threads"
    del c

    store = Store([str(tmp_path / "ec")])
    store.add_volume(1)
    v = store.find_volume(1)
    blob = bytes(range(256)) * 4
    n = 400
    for i in range(1, n + 1):
        v.write_needle(Needle(id=i, cookie=9, data=blob))
    v.read_only = True
    v.sync()
    base = v.file_name()
    encoder.write_ec_files(base, backend="numpy")
    encoder.write_sorted_file_from_idx(base)
    store.location_of(1).delete_volume(1)
    store_ec.mount_ec_shards(store, 1, "", range(14))
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        store_ec.read_ec_needle(store, 1, Needle(id=i, cookie=9),
                                cache=None)
    read_us = (time.perf_counter() - t0) / n * 1e6
    store.close()
    # healthy EC reads measure ~60-120 us here; 1000 us catches the
    # disabled path growing per-read work without flaking on VM load
    assert read_us <= 1000, \
        f"cache-disabled EC read {read_us:.0f} us/needle"


def test_degraded_decode_disabled_overhead(tmp_path):
    """The degraded decode fleet must be zero-cost until a degraded
    read actually happens (ISSUE 4 contract).

    Construction spawns nothing — no dispatcher, no reader pool — and
    HEALTHY reads through a server wired with the fleet never touch
    it: after hundreds of healthy EC needle reads with the decoder
    passed down the read path, the process still has no reads-* or
    ec-recover thread."""
    import threading

    from seaweedfs_tpu.ec import encoder, store_ec
    from seaweedfs_tpu.reads import DegradedReadFleet
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    def fleet_threads():
        return [t.name for t in threading.enumerate()
                if t.name.startswith(("reads-", "ec-recover"))]

    baseline = set(fleet_threads())   # earlier tests may have spawned
    fleet = DegradedReadFleet(backend="numpy")
    assert set(fleet_threads()) == baseline, \
        "constructing the decode fleet must not spawn threads"

    store = Store([str(tmp_path / "ec")])
    store.add_volume(1)
    v = store.find_volume(1)
    blob = bytes(range(256)) * 4
    n = 400
    for i in range(1, n + 1):
        v.write_needle(Needle(id=i, cookie=9, data=blob))
    v.read_only = True
    v.sync()
    base = v.file_name()
    encoder.write_ec_files(base, backend="numpy")
    encoder.write_sorted_file_from_idx(base)
    store.location_of(1).delete_volume(1)
    store_ec.mount_ec_shards(store, 1, "", range(14))
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        store_ec.read_ec_needle(store, 1, Needle(id=i, cookie=9),
                                decoder=fleet)
    read_us = (time.perf_counter() - t0) / n * 1e6
    store.close()
    assert set(fleet_threads()) == baseline, \
        "healthy reads must never wake the decode fleet"
    assert read_us <= 1000, \
        f"EC read with idle decode fleet {read_us:.0f} us/needle"


def test_ingest_pipeline_disabled_overhead(tmp_path, monkeypatch):
    """The ingest pipeline must be zero-cost until a multi-chunk body
    actually arrives (ISSUE 5 contract, the fleet/cache/scrub twin for
    the write subsystem).

    Gates. Construction: a filer built without -assign.leaseCount
    holds NO lease cache (the disabled assign path is one None check),
    and neither the filer's ingest pool, the volume server's replicate
    pool, nor operations' delete pool spawns a thread at construction.
    Serial path: a single-chunk upload and a single-replica (000)
    replicated write run entirely on the caller thread. Pipeline: only
    a genuinely multi-chunk body wakes the pool, and it spawns at most
    -ingest.parallelism threads."""
    import threading

    from seaweedfs_tpu.operation import operations
    from seaweedfs_tpu.operation.assign_lease import LeaseCache
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.util.fanout import FanOutPool

    PORT = 38888

    def ingest_threads():
        return [t.name for t in threading.enumerate()
                if t.name.startswith((f"ingest-{PORT}",
                                      f"replicate-{PORT}",
                                      "ingest-lease-refill"))]

    FanOutPool(8, "gate-idle")          # constructing a pool is free
    LeaseCache(count=8)                 # constructing the cache too
    assert ingest_threads() == []

    fs = FilerServer(master_url="127.0.0.1:1", port=PORT,
                     chunk_size=1024, ingest_parallelism=4)
    assert fs.leases is None, \
        "default-config filer must not construct a lease cache"
    assert ingest_threads() == [], \
        "constructing the filer must not spawn ingest threads"

    class _FakeAssign:
        def __init__(self):
            self.n = 0

        def __call__(self, master_url, **kw):
            self.n += 1
            return operations.Assignment(
                f"1,{self.n:x}000000aa", "stub:80", "stub:80", 1)

    monkeypatch.setattr(operations, "assign", _FakeAssign())
    monkeypatch.setattr(operations, "upload_data",
                        lambda url_fid, data, **kw: {"eTag": "t"})
    fs.upload_to_chunks(b"x" * 100)      # single chunk
    assert ingest_threads() == [], \
        "single-chunk upload must stay on the caller thread"
    fs.upload_to_chunks(b"x" * 5000)     # 5 chunks: NOW the pool wakes
    spawned = [t for t in ingest_threads()
               if t.startswith(f"ingest-{PORT}")]
    assert 0 < len(spawned) <= 4, \
        f"pipeline threads outside (0, parallelism]: {spawned}"

    d = tmp_path / "vs"
    d.mkdir()
    vs = VolumeServer(master_url="127.0.0.1:1", directories=[str(d)],
                      port=PORT, degraded_fleet=False)
    vs.store.add_volume(1)               # replication 000
    vs.replicated_write(1, Needle(id=1, cookie=9, data=b"solo"))
    assert not [t for t in ingest_threads()
                if t.startswith(f"replicate-{PORT}")], \
        "single-copy write must never wake the replication pool"
    vs.store.close()
    fs.filer.close()


def test_failpoints_disabled_overhead():
    """Failpoints must compile to a zero-cost no-op when unarmed
    (ISSUE 6 tentpole contract, the tracing-disabled twin for fault
    injection).

    The call-site pattern is `if failpoint._armed: failpoint.hit(...)`
    — one module-attribute truth test on the hot path. 200k iterations
    of exactly that pattern must stay far under a microsecond each
    (measured ~0.05 us; the 2 us ceiling only catches the regression
    class where a site accidentally calls into the spec table while
    unarmed). Arming and disarming must restore the zero-cost state."""
    from seaweedfs_tpu.resilience import failpoint

    assert not failpoint._armed, \
        "failpoints must be unarmed by default (no SEAWEED_FAILPOINTS)"
    t0 = time.perf_counter()
    for _ in range(200_000):
        if failpoint._armed:
            failpoint.hit("gate.site", peer="x")
    per_call = (time.perf_counter() - t0) / 200_000
    assert per_call < 2e-6, \
        f"unarmed failpoint check costs {per_call * 1e6:.3f} us/call"

    failpoint.arm("gate.site", "delay", arg=0.0)
    assert failpoint._armed
    failpoint.disarm()
    assert not failpoint._armed, "disarm must restore the zero-cost state"


def test_breaker_hedge_deadline_disabled_overhead(tmp_path):
    """Breakers, hedging, and deadline propagation must be zero-cost
    while disabled/unbudgeted (ISSUE 6 contract).

    Defaults: breakers off (module flag), hedging absent (servers hold
    hedger=None unless -resilience.hedge), deadlines unset (contextvar
    None). The per-request tax of the disabled layer is one flag check
    plus one ContextVar.get(); 200k iterations of that combined check
    hold a generous 2 us ceiling. Construction: a Hedger spawns no
    threads until its first multi-candidate fetch."""
    import threading

    from seaweedfs_tpu.resilience import Hedger, breaker, deadline
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.volume import VolumeServer

    assert not breaker.enabled, "breakers must be off by default"
    t0 = time.perf_counter()
    for _ in range(200_000):
        if deadline.get() is not None:
            raise AssertionError("no ambient deadline expected")
        if breaker.enabled:
            breaker.check("x")
    per_call = (time.perf_counter() - t0) / 200_000
    assert per_call < 2e-6, \
        f"disabled breaker+deadline check costs {per_call * 1e6:.3f} us"

    d = tmp_path / "vs"
    d.mkdir()
    vs = VolumeServer(master_url="127.0.0.1:1", directories=[str(d)])
    assert vs.hedger is None, \
        "default-config volume server must not construct a hedger"
    vs.store.close()
    fs = FilerServer(master_url="127.0.0.1:1", port=38889)
    assert fs.hedger is None, \
        "default-config filer must not construct a hedger"
    fs.filer.close()

    before = {t.name for t in threading.enumerate()}
    h = Hedger(name="gate-hedge")
    assert {t.name for t in threading.enumerate()} == before, \
        "constructing a hedger must not spawn threads"
    assert h.fetch([lambda: 42]) == 42   # single-candidate: inline
    assert {t.name for t in threading.enumerate()} == before, \
        "single-candidate fetches must stay on the caller thread"


def test_cluster_trace_disabled_overhead(tmp_path):
    """Cluster tracing + heat telemetry must be zero-cost while
    disabled (ISSUE 7 tentpole contract, the tracing/failpoint twin
    for the cross-hop observability layer).

    Gates. Defaults: the cluster tracer is off (module flag) and a
    default-config volume server holds NO heat tracker — the read
    path's heat branch is a None check. Micro: the ingress/egress seam
    pattern (`if cluster_trace._enabled:` + the disabled span() check)
    over 200k iterations stays far under a microsecond each. Threads:
    enabling and disabling the tracer spawns NOTHING — it is pure data
    structures; threads appear never, not merely "not until first
    sampled trace"."""
    import threading

    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.stats import cluster_trace, trace

    assert not cluster_trace.enabled(), \
        "cluster tracing must be off by default"
    assert not trace._cluster_enabled

    d = tmp_path / "vs"
    d.mkdir()
    vs = VolumeServer(master_url="127.0.0.1:1", directories=[str(d)])
    assert vs.heat is None, \
        "default-config volume server must not construct a heat tracker"
    vs.store.close()

    t0 = time.perf_counter()
    for _ in range(200_000):
        if cluster_trace._enabled:
            raise AssertionError("tracer unexpectedly enabled")
        trace.span("hot", vid=1)
    per_call = (time.perf_counter() - t0) / 200_000
    assert per_call < 5e-6, \
        f"disabled cluster-trace seam costs {per_call * 1e6:.2f} us/call"

    before = {t.name for t in threading.enumerate()}
    try:
        cluster_trace.enable(sample_fraction=0.0, slow_threshold_ms=50)
        ctx = cluster_trace.begin("gate", "get", "/x", None, server="g:1")
        cluster_trace.finish(ctx)
        assert {t.name for t in threading.enumerate()} == before, \
            "cluster tracing must never spawn threads"
    finally:
        cluster_trace.disable()
        cluster_trace.reset()

    # heat tracker: construction spawns nothing; record() holds a
    # generous per-call ceiling (it is a few list/dict ops)
    from seaweedfs_tpu.stats.heat import HeatTracker
    tr = HeatTracker()
    assert {t.name for t in threading.enumerate()} == before
    t0 = time.perf_counter()
    for i in range(100_000):
        tr.record(7, i & 0xFF)
    per_call = (time.perf_counter() - t0) / 100_000
    assert per_call < 10e-6, \
        f"heat record costs {per_call * 1e6:.2f} us/call"


def test_lifecycle_disabled_overhead(tmp_path):
    """The heat-driven lifecycle must be zero-cost while disabled
    (ISSUE 9 tentpole contract, the scrub/trace twin for the policy
    engine).

    Gates. Construction: a default-config master holds NO engine
    object and spawns no lifecycle thread — ever, not merely "not
    yet". Wire: a heat-less heartbeat serializes byte-identically to
    the pre-lifecycle format (field 17 absent), so heat-disabled
    clusters pay zero heartbeat bytes. Read path: the only lifecycle
    hook on the read path is the pre-existing -heat.track None check,
    asserted at one-flag-check cost."""
    import threading

    from seaweedfs_tpu.pb import master_pb2
    from seaweedfs_tpu.server import convert
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.storage.store import Store

    def lifecycle_threads():
        return [t.name for t in threading.enumerate()
                if "lifecycle" in t.name.lower()]

    ms = MasterServer(port=39991, meta_dir=str(tmp_path / "m"))
    assert ms.lifecycle is None, \
        "default-config master must not construct a lifecycle engine"
    assert lifecycle_threads() == [], \
        "a lifecycle thread exists without -lifecycle"

    # heartbeat byte-identity: a store's heartbeat through the full
    # convert path (heat absent) must serialize to EXACTLY the wire
    # bytes a pre-lifecycle Heartbeat message produces
    d = tmp_path / "vs"
    d.mkdir()
    vs = VolumeServer(master_url="127.0.0.1:1", directories=[str(d)],
                      degraded_fleet=False)
    assert vs.heat is None
    vs.store.add_volume(1)
    from seaweedfs_tpu.storage.needle import Needle
    vs.store.write_needle(1, Needle(id=1, cookie=9, data=b"hb"))
    hb = vs.store.collect_heartbeat()
    assert "volume_heats" not in hb, \
        "heat-disabled heartbeat dicts must not carry a heat key"
    got = convert.heartbeat_to_pb(hb, "dc", "r").SerializeToString()
    want = master_pb2.Heartbeat(
        ip=hb["ip"], port=hb["port"],
        public_url=hb.get("public_url", ""),
        max_volume_count=hb.get("max_volume_count", 0),
        max_file_key=hb.get("max_file_key", 0),
        data_center="dc", rack="r",
        volumes=[convert.volume_info_to_pb(v)
                 for v in hb.get("volumes", [])],
        ec_shards=[convert.ec_info_to_pb(e)
                   for e in hb.get("ec_shards", [])]).SerializeToString()
    assert got == want, \
        "heat-disabled heartbeat must be byte-identical to the " \
        "pre-lifecycle wire format"

    # read path: the lifecycle's only read-side branch is the
    # -heat.track None check — one attribute test per read
    t0 = time.perf_counter()
    for _ in range(200_000):
        if vs.heat is not None:
            raise AssertionError("default server grew a heat tracker")
    per_call = (time.perf_counter() - t0) / 200_000
    assert per_call < 2e-6, \
        f"disabled heat check costs {per_call * 1e6:.3f} us/call"
    vs.store.close()


def test_scrub_disabled_overhead(tmp_path):
    """Scrub must be zero-cost while disabled (ISSUE 3 contract, the
    test_tracing_disabled_overhead twin for the integrity subsystem).

    Three gates. Construction: a ScrubDaemon attached to a store
    spawns no thread and schedules no IO until start(). Read gate: the
    SEAWEED_VERIFY_READS check is one module flag, off by default.
    Engine: with an idle daemon attached the storage engine holds the
    same write/read floors as the bare-engine microbench above — the
    scrub subsystem adds NOTHING to the hot path (its only hook,
    the typed DataCorruptionError raise, fires on corrupt bytes)."""
    import threading

    from seaweedfs_tpu.scrub import ScrubDaemon
    from seaweedfs_tpu.storage import volume as volume_mod
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    def scrub_threads():
        # named assertion, not an active_count() equality: unrelated
        # threads from earlier tests in this process may EXIT while
        # this test runs, which must not flake the gate
        return [t.name for t in threading.enumerate()
                if "scrub" in t.name.lower()]

    assert not volume_mod.verify_reads_enabled()
    store = Store([str(tmp_path)])
    daemon = ScrubDaemon(store)   # attached but never started
    assert scrub_threads() == [], \
        "constructing the scrub daemon must not spawn threads"
    assert daemon.status()["state"] == "idle"

    store.add_volume(1)
    v = store.find_volume(1)
    blob = bytes(range(256)) * 4
    n = 2000
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        v.write_needle(Needle(id=i, cookie=9, data=blob))
    write_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        v.read_needle(Needle(id=i, cookie=9))
    read_us = (time.perf_counter() - t0) / n * 1e6
    store.close()
    assert scrub_threads() == []
    # identical floors to test_storage_engine_microbench: an idle
    # scrub daemon buys zero hot-path regression budget
    assert write_us <= 500, f"engine write {write_us:.0f} us/needle " \
        f"with idle scrub daemon attached"
    assert read_us <= 250, f"engine read {read_us:.0f} us/needle " \
        f"with idle scrub daemon attached"


def test_sanitizer_disabled_overhead():
    """The runtime concurrency sanitizer must be STRICTLY zero-cost
    when unarmed (ISSUE 8 contract — stronger than the other gates'
    one-flag-check: unarmed, `threading.Lock` must literally BE the
    untouched C factory, so every lock in the process is stock).

    Also proves arming is reversible and that the armed tax stays
    bounded enough for the chaos/cluster suites to run sanitized
    (conftest arms them by default)."""
    import threading

    from seaweedfs_tpu.util import sanitizer

    if os.environ.get("SEAWEED_SANITIZE"):
        pytest.skip("suite runs armed by explicit request")
    assert not sanitizer.armed(), \
        "sanitizer must be unarmed without SEAWEED_SANITIZE"
    assert threading.Lock is sanitizer._ORIG_LOCK, \
        "unarmed sanitizer must leave threading.Lock untouched"
    assert threading.RLock is sanitizer._ORIG_RLOCK
    assert not sanitizer.findings()

    # the unarmed acquire path is the stock C lock: 200k cycles bound
    lk = threading.Lock()
    t0 = time.perf_counter()
    for _ in range(200_000):
        with lk:
            pass
    stock = (time.perf_counter() - t0) / 200_000
    assert stock < 2e-6, f"stock lock cycle {stock * 1e6:.3f} us?!"

    # arm/disarm restores the zero-cost state exactly
    sanitizer.arm()
    try:
        assert sanitizer.armed()
        assert threading.Lock is not sanitizer._ORIG_LOCK
        wrapped = threading.Lock()
        t0 = time.perf_counter()
        for _ in range(20_000):
            with wrapped:
                pass
        armed_cost = (time.perf_counter() - t0) / 20_000
        # generous: armed is diagnostics mode, but it must stay usable
        # under the 32-way chaos scenarios (measured ~2-4 us)
        assert armed_cost < 100e-6, \
            f"armed lock cycle {armed_cost * 1e6:.1f} us"
    finally:
        sanitizer.disarm()
        sanitizer.reset()
    assert threading.Lock is sanitizer._ORIG_LOCK
    assert threading.RLock is sanitizer._ORIG_RLOCK


def test_scheduler_disabled_overhead():
    """The schedule explorer (ISSUE 10) must be STRICTLY zero-cost
    unarmed, same contract as the sanitizer gate above: importing the
    module leaves `threading.Lock` as the untouched C factory, patches
    nothing in `queue`/`time`, and spawns zero import-time threads.
    Arming is reversible, and an explore() run restores whatever
    factories it found (sanitizer composition included)."""
    import queue as queue_mod
    import threading
    import time as time_mod

    from seaweedfs_tpu.util import sanitizer
    from seaweedfs_tpu.util import scheduler

    if os.environ.get("SEAWEED_SCHED"):
        pytest.skip("suite runs armed by explicit request")
    assert not scheduler.armed(), \
        "scheduler must be unarmed without SEAWEED_SCHED"
    assert threading.Lock is sanitizer._ORIG_LOCK, \
        "unarmed scheduler must leave threading.Lock untouched"
    assert threading.RLock is sanitizer._ORIG_RLOCK
    assert threading.Event.__module__ == "threading"
    assert threading.Thread.__module__ == "threading"
    assert queue_mod.SimpleQueue.__module__ == "_queue"
    assert queue_mod.Queue.__module__ == "queue"
    assert time_mod.sleep.__module__ is None or \
        "scheduler" not in str(time_mod.sleep.__module__)

    # zero import-time threads: the module is imported (above) and the
    # process thread set contains no scheduler-born thread
    assert not [t for t in threading.enumerate()
                if "sched" in t.name.lower()]

    # the unarmed lock cycle is the stock C path (same bound as the
    # sanitizer gate)
    lk = threading.Lock()
    t0 = time.perf_counter()
    for _ in range(200_000):
        with lk:
            pass
    stock = (time.perf_counter() - t0) / 200_000
    assert stock < 2e-6, f"stock lock cycle {stock * 1e6:.3f} us?!"

    # arm/disarm restores the zero-cost state exactly, and a wrapper
    # created while armed keeps delegating afterwards
    scheduler.arm()
    try:
        assert scheduler.armed()
        assert threading.Lock is not sanitizer._ORIG_LOCK
        leftover = threading.Lock()
    finally:
        scheduler.disarm()
    assert threading.Lock is sanitizer._ORIG_LOCK
    assert threading.RLock is sanitizer._ORIG_RLOCK
    assert queue_mod.SimpleQueue.__module__ == "_queue"
    with leftover:            # delegate mode: plain real lock
        assert leftover.locked()
    assert not scheduler.armed()


def test_mesh_disabled_overhead(tmp_path):
    """The unified pod-scale mesh scheduler (ISSUE 11) must be
    zero-cost until a pod entry point actually runs with the mesh
    enabled — the house zero-cost-until-used contract.

    Three gates. Construction: a default VolumeServer (no -ec.mesh)
    carries ec_mesh_cfg=None — not an empty dict — so every consumer
    seam (batch encode, scrub verify, degraded decode) takes its
    `is None` fast path. Device query: running the default host-fleet
    batch encode end to end never builds a mesh object and never asks
    jax for devices (the lazily-cached `_default_mesh`/`_shardings`
    stay cold). Threads: no mesh-read or other mesh-born thread exists
    before, during, or after."""
    import threading

    from seaweedfs_tpu.ec import encoder, store_ec
    from seaweedfs_tpu.parallel import mesh_fleet
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.storage.needle import Needle

    def mesh_threads():
        return [t.name for t in threading.enumerate()
                if t.name.startswith("mesh-")]

    # deltas, not absolutes: earlier tests in this process may have
    # legitimately built the default mesh / run mesh passes
    mesh_misses = mesh_fleet._default_mesh.cache_info().misses
    shard_misses = mesh_fleet._shardings.cache_info().misses
    baseline = set(mesh_threads())

    d = tmp_path / "vs"
    d.mkdir()
    vs = VolumeServer(master_url="127.0.0.1:1", directories=[str(d)],
                      port=18999, ec_encoder="numpy")
    assert vs.ec_mesh_cfg is None, \
        "default server must carry NO mesh config (None, not {})"
    assert vs.degraded.use_mesh is False
    assert vs.scrub.mesh_cfg is None

    # the default batch-encode path end to end: host fleet only
    blob = bytes(range(256)) * 4
    for vid in (1, 2):
        vs.store.add_volume(vid)
        v = vs.store.find_volume(vid)
        for i in range(1, 40):
            v.write_needle(Needle(id=i, cookie=9, data=blob))
    store_ec.generate_ec_shards_batch(vs.store, [1, 2],
                                      backend="numpy",
                                      mesh_cfg=vs.ec_mesh_cfg)
    vs.store.close()

    assert set(mesh_threads()) == baseline, \
        "default encode path must never spawn mesh threads"
    assert mesh_fleet._default_mesh.cache_info().misses == mesh_misses, \
        "default path must never query jax devices for a mesh"
    assert mesh_fleet._shardings.cache_info().misses == shard_misses, \
        "default path must never build mesh shardings"


def test_meta_disabled_overhead(tmp_path):
    """The metadata plane's caches (ISSUE 12) must be STRICTLY
    zero-cost while disabled — the house contract.

    Gates. Module: importing wdclient/lookup_cache leaves the seam
    disabled with NO cache constructed anywhere (env-armed runs are
    skipped, mirroring the scheduler gate). Construction: a default
    FilerServer (no -meta.*) carries listing_cache=None, an unhooked
    event log (on_append is None), and a cacheless MasterClient — the
    wired call sites are each ONE None/flag check. Behavior: the
    disabled operations.lookup_many is exactly a loop over lookup()
    and constructs nothing. Threads: none of it spawns any."""
    import threading

    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.wdclient import lookup_cache
    from seaweedfs_tpu.wdclient.masterclient import MasterClient

    if os.environ.get("SEAWEED_META_LOOKUP_TTL_S"):
        pytest.skip("suite runs with the meta cache armed by request")

    assert not lookup_cache.enabled, \
        "lookup cache must be disabled without -meta.lookupTTL/env"
    assert not lookup_cache._caches, \
        "no process-wide cache may exist while disabled"

    before = {t.name for t in threading.enumerate()}

    fs = FilerServer(master_url="127.0.0.1:1", port=18996)
    try:
        assert fs.listing_cache is None, \
            "default filer must not construct a listing cache"
        assert fs.filer.listing_cache is None
        assert fs.filer.meta_log.on_append is None, \
            "default event log must not carry an invalidation hook"
        assert fs.master_client._lookup_cache is None
        assert fs.master_client.lookup_cache_enabled is False

        # the disabled list path is the pre-ISSUE-12 store walk
        from seaweedfs_tpu.filer.filer import new_entry
        fs.filer.create_entry("/gate", new_entry("x"))
        assert [e.name for e in fs.filer.list_entries("/gate")] == ["x"]
        assert fs.listing_cache is None and not lookup_cache._caches
    finally:
        fs.filer.close()

    # constructing the caches directly spawns nothing either (they are
    # pure data structures; the batch leader runs on caller threads)
    from seaweedfs_tpu.filer.listing_cache import ListingCache
    lc = ListingCache(1 << 20)
    cc = lookup_cache.CoalescingLookupCache(lambda vids: {},
                                            coalesce_s=0)
    del lc, cc
    mc = MasterClient(["127.0.0.1:1"], client_name="gate")
    assert mc._lookup_cache is None

    after = {t.name for t in threading.enumerate()}
    # the event log's lazily-spawned flusher belongs to the
    # pre-existing append machinery (the create_entry above), not to
    # the meta plane; nothing ELSE may have appeared
    grown = after - before - {"log-buffer-flush"}
    assert len(grown) == 0, f"disabled meta plane spawned {grown}"


def test_serve_async_disabled_overhead(tmp_path):
    """The async serving core (ISSUE 13) must be STRICTLY zero-cost
    while -serve.async is off — the house contract.

    Gates. Construction: make_http_server without the flag builds the
    stock TrackingHTTPServer — no AsyncHTTPServer, no selector, no
    state-machine objects, no worker pool (proved by poisoning the
    constructor when the module is already imported, and by the module
    staying unimported when it is not). Hot path: the handler-side
    seam is ONE class-attribute read (FastHandler.async_conn is None)
    and bodiless requests build no BodyReader. Threads: a threaded
    server answering requests grows exactly the connection threads the
    stock model always grew — nothing async-named."""
    import sys
    import threading
    import urllib.request

    import seaweedfs_tpu.util.http_server as hs

    mod = sys.modules.get("seaweedfs_tpu.util.async_server")
    poisoned = []
    if mod is not None:
        # another test imported the async core: any construction with
        # the flag off would trip this
        orig_init = mod.AsyncHTTPServer.__init__

        def boom(*a, **kw):
            poisoned.append(a)
            raise AssertionError(
                "AsyncHTTPServer constructed with -serve.async off")
        mod.AsyncHTTPServer.__init__ = boom
    try:
        class H(hs.FastHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                # the one disabled-path check handlers may pay
                assert self.async_conn is None
                assert not isinstance(self.rfile, hs.BodyReader), \
                    "bodiless GET must not build a BodyReader"
                self.fast_reply(200, b"ok")

        for serve in (None, hs.ServeConfig()):
            srv = hs.make_http_server(("127.0.0.1", 0), H,
                                      role="gate", serve=serve)
            assert type(srv) is hs.TrackingHTTPServer
            t = threading.Thread(target=srv.serve_forever, daemon=True)
            t.start()
            try:
                with urllib.request.urlopen(
                        "http://127.0.0.1:%d/x"
                        % srv.server_address[1]) as r:
                    assert r.read() == b"ok"
            finally:
                srv.shutdown()
                srv.server_close()
        assert not poisoned
        if mod is None:
            assert "seaweedfs_tpu.util.async_server" not in \
                sys.modules, \
                "flag-off construction imported the async core"
        # the handler seam is a class attribute, not per-instance
        # state: no default instance carries async machinery
        assert "async_conn" not in hs.FastHandler.__dict__ or \
            hs.FastHandler.async_conn is None
        assert not any("serve-" in t.name or "async" in t.name.lower()
                       for t in threading.enumerate()), \
            "disabled serving core left async-named threads"
    finally:
        if mod is not None:
            mod.AsyncHTTPServer.__init__ = orig_init


def test_qos_disabled_overhead():
    """The multi-tenant QoS plane (ISSUE 19) must cost nothing while
    -qos is off: every consumer seam holds a module-global None, the
    per-call check is one load+is-check, FanOutPool submits never
    build a weighted queue, the tenant contextvar is never set, and
    configuring the manager spawns zero threads (buckets are pure
    clock math — there is no refill daemon to leak)."""
    import threading

    from seaweedfs_tpu import qos, rpc
    from seaweedfs_tpu.qos.admission import QosConfig, QosManager
    from seaweedfs_tpu.stats import metrics
    from seaweedfs_tpu.util import async_server, fanout, http_client
    from seaweedfs_tpu.util.fanout import FanOutPool

    # disabled state: every seam is a plain None module global
    assert qos._manager is None, "qos must be off by default"
    assert fanout._qos_sched is None
    assert async_server._qos is None
    assert metrics._qos_http is None
    assert http_client._qos_tenant is None
    assert rpc._qos_tenant is None
    from seaweedfs_tpu.qos import tenant
    assert tenant.current.get() is None, \
        "no ambient tenant may exist while qos is off"

    # the per-request seam is one None check: 200k cycles bound
    t0 = time.perf_counter()
    for _ in range(200_000):
        if metrics._qos_http is not None:   # the instrument-wrapper seam
            raise AssertionError
        if fanout._qos_sched is not None:   # the pool submit seam
            raise AssertionError
    per_call = (time.perf_counter() - t0) / 200_000
    assert per_call < 2e-6, f"qos-off seam check {per_call * 1e6:.3f} us"

    # qos-off pool submits take the stock FIFO path, never the WFQ
    pool = FanOutPool(size=2, name="qos-gate-pool")
    try:
        futs = [pool.submit(lambda i=i: i) for i in range(8)]
        for f in futs:
            f.wait(5)
        assert pool._wfq is None, \
            "qos-off submit built a weighted queue"
    finally:
        pool.stop()

    # constructing + configuring the manager spawns no threads
    before = {t.ident for t in threading.enumerate()}
    mgr = QosManager(QosConfig(request_rate=100.0, bytes_mbps=10.0,
                               global_request_rate=1000.0))
    mgr.admit("gate", nbytes=4096)
    try:
        qos.configure(QosConfig())
        assert qos.enabled()
    finally:
        qos.reset()
    after = {t.ident for t in threading.enumerate()}
    assert after == before, "qos construction spawned threads"
    assert not any("qos" in t.name.lower()
                   for t in threading.enumerate()), \
        "qos left named threads behind"

    # reset() restores the never-configured state exactly
    assert qos._manager is None and fanout._qos_sched is None
    assert async_server._qos is None and metrics._qos_http is None
    assert http_client._qos_tenant is None and rpc._qos_tenant is None
