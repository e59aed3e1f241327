"""Exposition correctness + span tracer (stats/metrics.py, stats/trace.py).

Exposition bugs are silent: Prometheus scrapes keep "working" while the
parser drops or mis-buckets samples, so the text format's contracts —
bucket cumulativity, +Inf == _count, label escaping — are pinned here
byte-for-byte. The tracer tests pin the span model: zero-allocation
no-op when disabled, same-thread nesting, cross-thread handoff tokens,
Chrome trace-event export.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import (
    MetricsPushErrorCounter, Registry, loop_pushing_metric,
    start_metrics_server)


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


# -- exposition ---------------------------------------------------------------

class TestExposition:
    def test_label_values_escaped(self):
        """Backslash, double-quote and newline in label VALUES must be
        escaped per the text-format spec or the exposition is
        unparseable."""
        reg = Registry()
        c = reg.counter("esc_total", "h", ("path",))
        c.labels('a"b').inc()
        c.labels("c\\d").inc()
        c.labels("e\nf").inc()
        text = reg.render()
        assert 'esc_total{path="a\\"b"} 1.0' in text
        assert 'esc_total{path="c\\\\d"} 1.0' in text
        assert 'esc_total{path="e\\nf"} 1.0' in text
        assert "\ne\nf" not in text  # no raw newline mid-sample

    def test_histogram_buckets_cumulative(self):
        """le buckets are CUMULATIVE: each bucket counts every
        observation <= its bound, +Inf equals _count, _sum is the
        total."""
        reg = Registry()
        h = reg.histogram("lat", "h", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        text = reg.render()
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="1.0"} 3' in text
        assert 'lat_bucket{le="10.0"} 4' in text
        assert 'lat_bucket{le="+Inf"} 5' in text
        assert "lat_count 5" in text
        assert "lat_sum 56.05" in text

    def test_histogram_boundary_value_included(self):
        """An observation exactly on a bucket bound lands IN that
        bucket (le = less-or-equal)."""
        reg = Registry()
        h = reg.histogram("b", "h", buckets=(1.0, 2.0))
        h.observe(1.0)
        text = reg.render()
        assert 'b_bucket{le="1.0"} 1' in text

    def test_concurrent_observe_many_threads(self):
        """observe() from many threads must lose no samples and keep
        the cumulativity invariant (bucket counts monotone, +Inf ==
        _count == total observations)."""
        reg = Registry()
        h = reg.histogram("conc", "h", ("op",), buckets=(0.5, 1.5))
        child = h.labels("x")
        n_threads, per_thread = 8, 2000

        def work():
            for i in range(per_thread):
                child.observe((i % 3))  # 0, 1, 2 -> buckets 1, 2, inf

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * per_thread
        assert child.count == total
        # exact: 0 -> both buckets, 1 -> second bucket only, 2 -> inf
        zeros = sum(1 for i in range(per_thread) if i % 3 == 0) * n_threads
        ones = sum(1 for i in range(per_thread) if i % 3 == 1) * n_threads
        assert child.counts[0] == zeros
        assert child.counts[1] == zeros + ones
        text = reg.render()
        assert f'conc_bucket{{op="x",le="+Inf"}} {total}' in text
        assert f'conc_count{{op="x"}} {total}' in text


# -- metrics HTTP handler -----------------------------------------------------

class TestMetricsEndpoint:
    @pytest.fixture()
    def srv(self):
        reg = Registry()
        reg.counter("up_total", "x").inc()
        srv = start_metrics_server(0, registry=reg, ip="127.0.0.1",
                                   role="volumeServer")
        srv._test_port = srv.server_address[1]
        yield srv
        srv.shutdown()
        srv.server_close()

    def _get(self, srv, path):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{srv._test_port}{path}", timeout=5)

    def test_metrics_ok(self, srv):
        with self._get(srv, "/metrics") as r:
            assert "up_total 1.0" in r.read().decode()

    def test_healthz_role_and_uptime(self, srv):
        with self._get(srv, "/healthz") as r:
            doc = json.load(r)
        assert doc["role"] == "volumeServer"
        assert doc["uptime_seconds"] >= 0

    def test_unknown_path_404(self, srv):
        with pytest.raises(urllib.error.HTTPError) as ei:
            self._get(srv, "/somewhere/else")
        assert ei.value.code == 404

    def test_debug_trace_serves_chrome_json(self, srv):
        trace.enable()
        with trace.span("unit.test"):
            pass
        with self._get(srv, "/debug/trace") as r:
            doc = json.load(r)
        names = [e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"]
        assert "unit.test" in names


# -- push loop ----------------------------------------------------------------

def test_push_loop_counts_errors_and_logs_transitions(caplog):
    """A dead gateway increments SeaweedFS_metrics_push_errors_total
    every attempt but logs only the ok->failing TRANSITION, not every
    attempt."""
    import logging
    reg = Registry()
    before = MetricsPushErrorCounter.labels().value
    stop = threading.Event()
    with caplog.at_level(logging.WARNING, logger="seaweedfs_tpu.metrics"):
        t = loop_pushing_metric("job", "inst", "127.0.0.1:1",  # closed port
                                interval_seconds=0.05, registry=reg,
                                stop_event=stop)
        deadline = time.monotonic() + 10
        while MetricsPushErrorCounter.labels().value < before + 3 and \
                time.monotonic() < deadline:
            time.sleep(0.02)
        stop.set()
        t.join(timeout=5)
    assert MetricsPushErrorCounter.labels().value >= before + 3
    failing_logs = [r for r in caplog.records
                    if "metrics push" in r.getMessage()
                    and "failing" in r.getMessage()]
    assert len(failing_logs) == 1, \
        f"expected ONE transition log, got {len(failing_logs)}"


# -- tracer -------------------------------------------------------------------

class TestTrace:
    def test_disabled_is_shared_noop(self):
        """Disabled tracing allocates nothing: every span() call
        returns the same no-op object and records nothing."""
        assert trace.span("a") is trace.span("b") is trace.NOOP
        with trace.span("c", k=1):
            pass
        assert trace.spans() == []
        assert trace.handoff() is None

    def test_same_thread_nesting(self):
        trace.enable()
        with trace.span("outer") as outer:
            with trace.span("inner") as inner:
                pass
        got = {s.name: s for s in trace.spans()}
        assert got["inner"].parent_id == outer.id
        assert got["outer"].parent_id is None
        assert got["inner"].dur <= got["outer"].dur

    def test_cross_thread_handoff(self):
        """A handoff token parents a span opened on ANOTHER thread
    under the minting span — the pipeline-stage contract."""
        trace.enable()
        seen = {}

        def stage_two(token):
            with trace.span("stage2", parent=token) as s:
                seen["tid"] = s.tid

        with trace.span("stage1") as s1:
            tok = s1.token()
            t = threading.Thread(target=stage_two, args=(tok,))
            t.start()
            t.join()
        got = {s.name: s for s in trace.spans()}
        assert got["stage2"].parent_id == got["stage1"].id
        assert got["stage2"].tid != got["stage1"].tid

    def test_ring_is_bounded(self):
        trace.enable(capacity=16)
        for i in range(100):
            with trace.span("s", i=i):
                pass
        items = trace.spans()
        assert len(items) == 16
        assert items[-1].tags["i"] == 99  # newest kept, oldest evicted
        trace.enable(capacity=trace.DEFAULT_CAPACITY)

    def test_chrome_trace_shape(self):
        trace.enable()
        with trace.span("alpha", vid=3):
            pass
        doc = json.loads(trace.chrome_trace_json())
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xs and xs[-1]["name"] == "alpha"
        assert xs[-1]["args"]["vid"] == 3
        assert xs[-1]["dur"] >= 0
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert any(m["name"] == "thread_name" for m in metas)

    def test_rollup_and_busy_union(self):
        trace.enable()
        t0 = time.perf_counter()
        with trace.span("work"):
            time.sleep(0.05)
        with trace.span("work"):
            time.sleep(0.02)
        t1 = time.perf_counter()
        roll = trace.rollup()
        assert roll["work"]["count"] == 2
        assert roll["work"]["total_s"] >= 0.06
        covered = trace.busy_union_s(trace.spans(), t0, t1,
                                     prefixes=("work",))
        assert covered >= 0.06
        assert covered <= (t1 - t0) + 1e-6

    def test_busy_union_merges_overlaps(self):
        """Two spans over the same interval must not double-count."""
        a = trace.Span("x", None, {})
        a.t0, a.dur = 10.0, 1.0
        b = trace.Span("x", None, {})
        b.t0, b.dur = 10.5, 1.0
        assert abs(trace.busy_union_s([a, b], 10.0, 12.0) - 1.5) < 1e-9


# -- fleet pipeline metrics ---------------------------------------------------

def test_fleet_encode_populates_stage_metrics(tmp_path):
    """One fleet encode must leave non-zero samples in every
    fleet-stage family (the acceptance gate: stage attribution for
    free on any ec.encode)."""
    import numpy as np

    from seaweedfs_tpu.ec import fleet
    from seaweedfs_tpu.stats.metrics import (
        REGISTRY, FleetDispatchedBytesCounter)

    rng = np.random.default_rng(23)
    bases = []
    for v in range(3):
        base = str(tmp_path / f"m{v}")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, 2 << 20, dtype=np.uint8)
                    .tobytes())
        bases.append(base)
    bytes_before = FleetDispatchedBytesCounter.labels().value
    fleet.fleet_write_ec_files(bases, backend="numpy")
    assert FleetDispatchedBytesCounter.labels().value >= \
        bytes_before + 3 * (2 << 20)
    text = REGISTRY.render()
    assert 'SeaweedFS_fleet_stage_seconds_bucket{stage="read"' in text
    assert 'SeaweedFS_fleet_stage_seconds_count{stage="retire"}' in text
    assert 'SeaweedFS_fleet_stage_seconds_count{stage="write"}' in text
    assert 'SeaweedFS_fleet_stage_seconds_count{stage="dispatch"}' in text
    assert "SeaweedFS_fleet_dispatch_batch_spans_count" in text
    assert "SeaweedFS_fleet_reader_queue_depth" in text
    assert "SeaweedFS_fleet_writer_lane_backlog" in text


def test_fleet_encode_traced_spans_cover_stages(tmp_path):
    """With tracing on, a fleet encode emits spans for every stage,
    parented under fleet.encode, and the union of stage spans covers
    most of the wall time (the bench --trace contract, held loosely
    here: a tiny encode on a loaded CI VM has startup overhead a real
    bench run amortizes)."""
    import numpy as np

    from seaweedfs_tpu.ec import fleet

    rng = np.random.default_rng(29)
    bases = []
    for v in range(4):
        base = str(tmp_path / f"t{v}")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, 2 << 20, dtype=np.uint8)
                    .tobytes())
        bases.append(base)
    trace.enable()
    t0 = time.perf_counter()
    fleet.fleet_write_ec_files(bases, backend="numpy")
    t1 = time.perf_counter()
    items = trace.spans()
    names = {s.name for s in items}
    for stage in ("fleet.encode", "fleet.read", "fleet.dispatch",
                  "fleet.rs", "fleet.retire", "fleet.write"):
        assert stage in names, f"missing {stage} spans (got {names})"
    root = next(s for s in items if s.name == "fleet.encode")
    reads = [s for s in items if s.name == "fleet.read"]
    assert all(r.parent_id == root.id for r in reads)
    covered = trace.busy_union_s(
        items, t0, t1, prefixes=("fleet.read", "fleet.dispatch",
                                 "fleet.rs", "fleet.retire",
                                 "fleet.write"))
    assert covered / (t1 - t0) >= 0.5, \
        f"stage spans cover only {covered / (t1 - t0):.0%} of wall"


def test_fleet_encode_shards_identical_with_tracing(tmp_path):
    """Tracing must be purely observational: shard bytes with tracing
    enabled match a serial untraced encode."""
    import numpy as np

    from seaweedfs_tpu.ec import encoder, fleet

    rng = np.random.default_rng(31)
    data = rng.integers(0, 256, (3 << 20) + 123, dtype=np.uint8).tobytes()
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    for base in (a, b):
        with open(base + ".dat", "wb") as f:
            f.write(data)
    encoder.write_ec_files(a, backend="numpy")
    trace.enable()
    fleet.fleet_write_ec_files([b], backend="numpy")
    for sid in range(14):
        pa = encoder.shard_file_name(a, sid)
        pb = encoder.shard_file_name(b, sid)
        assert open(pa, "rb").read() == open(pb, "rb").read(), \
            f"shard {sid} diverged under tracing"


# -- phase timers: the dispatch layer and the scheduler's waits ---------------

_RS_PHASES = ("stage", "place", "enqueue", "wait", "fetch", "unstage")


def _hist_counts(family, values):
    return {v: family.labels(v).count for v in values}


def _moved(family, values, before):
    return {v: family.labels(v).count - before[v] for v in values}


@pytest.mark.parametrize("placement", ["mesh", "device", "default"])
@pytest.mark.parametrize("batched, lent", [
    (True, False), (False, False), (False, True)])
def test_rs_dispatch_phases_count_and_nest(placement, batched, lent,
                                           monkeypatch):
    """One apply_matrix_async(...).result() observes each phase of
    SeaweedFS_rs_dispatch_seconds the expected number of times — place,
    enqueue, wait and fetch once a slab; stage and unstage once a slab
    (slice/pad, copy-out: into a fresh array or the one the caller
    lent) plus once for the flatten / the moveaxis back of a batched
    input — and its rs.* spans nest under the span the caller has open.
    Counts only: no wall-clock assertion."""
    import jax
    import numpy as np

    from seaweedfs_tpu.ops import rs_kernel
    from seaweedfs_tpu.ops.rs_code import (
        DATA_SHARDS, PARITY_SHARDS, ReedSolomon, coding_matrix)
    from seaweedfs_tpu.stats.metrics import RsDispatchSecondsHistogram

    slab = rs_kernel._MIN_SLAB
    monkeypatch.setattr(rs_kernel, "_MAX_SLAB", slab)
    device = None
    if placement == "device":
        device = jax.devices()[0]
    elif placement == "default":
        monkeypatch.setattr(rs_kernel, "_lane_sharding", lambda: None)
    rng = np.random.default_rng(41)
    shape = (3, DATA_SHARDS, 70_000) if batched else (DATA_SHARDS, 210_000)
    data = rng.integers(0, 256, shape, dtype=np.uint8)
    n_slabs = -(-210_000 // slab)
    matrix = np.asarray(coding_matrix())[DATA_SHARDS:]

    out = np.empty((PARITY_SHARDS, 210_000), dtype=np.uint8) if lent \
        else None
    before = _hist_counts(RsDispatchSecondsHistogram, _RS_PHASES)
    trace.enable()
    with trace.span("caller") as caller:
        got = rs_kernel.apply_matrix_async(matrix, data, device=device,
                                           out=out).result()
    assert (got is out) == lent
    moved = _moved(RsDispatchSecondsHistogram, _RS_PHASES, before)
    extra = 1 if batched else 0
    assert moved == {"stage": n_slabs + extra, "place": n_slabs,
                     "enqueue": n_slabs, "wait": n_slabs,
                     "fetch": n_slabs, "unstage": n_slabs + extra}
    assert np.array_equal(got, ReedSolomon(backend="numpy").encode(data))

    rs = [s for s in trace.spans() if s.name.startswith("rs.")]
    per_name = {}
    for s in rs:
        per_name[s.name[3:]] = per_name.get(s.name[3:], 0) + 1
        assert s.parent_id == caller.id, s.name
    assert per_name == moved
    assert [s.tags["bytes"] for s in rs if s.name == "rs.place"] == \
        [DATA_SHARDS * slab] * n_slabs        # padding included
    assert [s.tags["bytes"] for s in rs if s.name == "rs.fetch"] == \
        [PARITY_SHARDS * slab] * n_slabs


@pytest.fixture(scope="module")
def traced_jax_fleet_encode(tmp_path_factory):
    """One small fleet encode on the jax backend with tracing on: the
    spans, the thread names, what the wait family counted, and the
    volumes beside a serial numpy encode of the same bytes."""
    import numpy as np

    from seaweedfs_tpu.ec import encoder, fleet
    from seaweedfs_tpu.stats.metrics import FleetWaitSecondsHistogram

    large, small = 2048, 256
    row = 10 * small
    root = tmp_path_factory.mktemp("traced_fleet")
    rng = np.random.default_rng(43)
    bases, twins = [], []
    for i, size in enumerate((3 * row + 123, row, 700)):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        for names, tag in ((bases, "f"), (twins, "s")):
            base = str(root / f"{tag}{i}")
            with open(base + ".dat", "wb") as f:
                f.write(data)
            names.append(base)
    for twin in twins:
        encoder.write_ec_files(twin, backend="numpy", large_block=large,
                               small_block=small)
    waits = ("reader", "retire_slot", "lane_from_pack", "lane_from_retire",
             "staging")
    before = _hist_counts(FleetWaitSecondsHistogram, waits)
    trace.disable()
    trace.clear()
    trace.enable()
    try:
        # two rows a dispatch, so several dispatches and retires
        fleet.fleet_write_ec_files(bases, backend="jax", large_block=large,
                                   small_block=small, chunk=2 * row)
        spans = trace.spans()
        threads = dict(trace._thread_names)
    finally:
        trace.disable()
        trace.clear()
    return {"spans": spans, "threads": threads, "bases": bases,
            "twins": twins, "caller_tid": threading.get_ident(),
            "waits": _moved(FleetWaitSecondsHistogram, waits, before)}


@pytest.mark.parametrize("stage, thread, names", [
    ("fleet.encode", "caller",
     ("fleet.dispatch", "fleet.wait.reader", "fleet.wait.staging")),
    ("fleet.dispatch", "caller",
     ("fleet.pack", "rs.stage", "rs.place", "rs.enqueue")),
    ("fleet.retire", "fleet-retire",
     ("rs.wait", "rs.fetch", "rs.unstage", "fleet.wait.lane_from_retire")),
])
def test_fleet_phase_spans_lie_under_their_stage(traced_jax_fleet_encode,
                                                 stage, thread, names):
    """The decomposition the benchmark reads: what the dispatch layer
    and the scheduler time inside fleet.dispatch (packing thread) and
    inside fleet.retire (retire thread) is recorded under a span of
    that stage, on that thread."""
    run = traced_jax_fleet_encode
    by_id = {s.id: s for s in run["spans"]}
    for name in names:
        found = [s for s in run["spans"] if s.name == name]
        assert found, f"no {name} span (got {sorted({s.name for s in run['spans']})})"
        for s in found:
            parent = by_id[s.parent_id]
            if parent.name == "fleet.pass.fill":
                # a wait before the pass's first dispatch lies in the
                # pass's `fill` part, itself a span under the root
                parent = by_id[parent.parent_id]
            assert parent.name == stage, (name, parent.name)
            assert parent.tid == s.tid
            if thread == "caller":
                assert s.tid == run["caller_tid"]
            else:
                assert run["threads"][s.tid] == thread


def test_fleet_reader_wait_counts_one_observation_a_span(
        traced_jax_fleet_encode):
    run = traced_jax_fleet_encode
    reads = [s for s in run["spans"] if s.name == "fleet.read"]
    assert len(reads) == 6          # rows of one volume each: 4 + 1 + 1
    assert run["waits"]["reader"] == len(reads)
    assert len([s for s in run["spans"]
                if s.name == "fleet.wait.reader"]) == len(reads)
    # one retire slot a dispatch; one lane put a data write and a parity
    dispatches = len([s for s in run["spans"] if s.name == "fleet.dispatch"])
    assert run["waits"]["retire_slot"] == dispatches
    # one staging buffer a dispatch, waited for where the span is planned
    assert run["waits"]["staging"] == dispatches == 3
    assert len([s for s in run["spans"]
                if s.name == "fleet.wait.staging"]) == dispatches
    assert run["waits"]["lane_from_pack"] == len(reads)
    assert run["waits"]["lane_from_retire"] == len(reads)


def test_fleet_jax_shards_equal_serial_numpy_with_timers_in(
        traced_jax_fleet_encode):
    from seaweedfs_tpu.ec.encoder import shard_file_name
    run = traced_jax_fleet_encode
    for got, want in zip(run["bases"], run["twins"]):
        for sid in range(14):
            with open(shard_file_name(got, sid), "rb") as g, \
                    open(shard_file_name(want, sid), "rb") as w:
                assert g.read() == w.read(), f"shard {sid} of {got} differs"


# -- the ring's spans in the profiler's trace ---------------------------------

def test_spans_stand_in_the_profiler_trace_on_its_clock(tmp_path):
    """With the ring on in a process that has loaded jax, a span is also
    a TraceAnnotation: it stands on a /host: plane of the .xplane.pb,
    and its start there is the ring's start shifted by a sync annotation
    taken beside a host-clock reading (what benchmark/run.py does)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # a host reading before each sync annotation: a delay between
        # the two only widens the difference, so the least is the shift
        sync_host = []
        for _ in range(5):
            sync_host.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("test.sync"):
                pass
        with trace.span("mirror.outer"):
            with trace.span("mirror.inner"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    ring = {s.name: s for s in trace.spans()}
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    found = {"test.sync": [], "mirror.outer": [], "mirror.inner": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in found:
                    found[e.name].append((e.start_ns / 1e9,
                                          e.duration_ns / 1e9))
    assert [len(v) for v in found.values()] == [5, 1, 1], found
    shift = min(at - host for (at, _), host in
                zip(sorted(found["test.sync"]), sync_host))
    for name in ("mirror.outer", "mirror.inner"):
        (start, dur), = found[name]
        assert abs(start - (ring[name].t0 + shift)) < 1e-3, name
        assert dur >= ring[name].dur


def test_stats_never_imports_jax():
    """Master and filer processes load stats/ and never jax: the
    profiler mirror looks jax up in sys.modules only."""
    import subprocess
    import sys
    code = ("import sys; from seaweedfs_tpu.stats import trace, metrics; "
            "trace.enable(); "
            "s = trace.span('x'); s.__enter__(); s.__exit__(None, None, None); "
            "assert 'jax' not in sys.modules; assert trace.spans()[0]._ann is None")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
