"""A job's wall, accounted from inside: every pass of the fleet
scheduler is timed as a whole and cut into fill, steady and drain
(`SeaweedFS_fleet_pass_seconds`, `..._pass_part_seconds`), the store's
steps around it (`SeaweedFS_store_ec_seconds`) — and every series a
file of `benchmark/layer_metrics/` names is one the program exposes."""

import glob
import json
import os
import re
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import fleet, store_ec
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import (
    REGISTRY, FleetPassPartSecondsHistogram, FleetPassSecondsHistogram,
    StoreEcSecondsHistogram)
from seaweedfs_tpu.storage.store import Store
from tests.test_store_ec import fill_volume

PASSES = ("encode", "rebuild", "verify")
SMALL = 256
ROW = 10 * SMALL


def _volumes(tmp_path, n=2, rows=5):
    rng = np.random.default_rng(41)
    bases = []
    for v in range(n):
        base = str(tmp_path / f"v{v}")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, rows * ROW + 7 * v,
                                 dtype=np.uint8).tobytes())
        bases.append(base)
    return bases


def _encode(bases, backend):
    fleet.fleet_write_ec_files(bases, backend=backend, large_block=SMALL << 8,
                               small_block=SMALL, chunk=2 * ROW)


def _run_pass(kind, bases, backend):
    """One pass of `kind` over `bases`; what it needs first (the shard
    files, a loss) is made before the caller looks at the counters."""
    if kind == "encode":
        return lambda: _encode(bases, backend)
    _encode(bases, backend)
    if kind == "rebuild":
        for base in bases:
            os.remove(shard_file_name(base, 3))
        return lambda: fleet.fleet_rebuild_ec_files(bases, backend=backend,
                                                    chunk=2 * ROW)
    return lambda: fleet.fleet_verify_ec_files(bases, backend=backend,
                                               chunk=2 * ROW)


def _pass_state():
    whole = {k: FleetPassSecondsHistogram.labels(k) for k in PASSES}
    part = {(k, p): FleetPassPartSecondsHistogram.labels(k, p)
            for k in PASSES for p in ("fill", "drain")}
    return ({k: (c.count, c.total) for k, c in whole.items()},
            {k: (c.count, c.total) for k, c in part.items()})


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("kind", PASSES)
def test_a_pass_is_timed_once_as_a_whole_and_at_both_ends(kind, backend,
                                                          tmp_path):
    """One call, one pass: the family's count of that kind rises by
    exactly one, `fill` and `drain` are observed once each and fit
    inside the whole — and with the ring off the timers allocate no
    Span (the span-id counter does not advance)."""
    assert not trace.is_enabled()
    run = _run_pass(kind, _volumes(tmp_path), backend)
    whole0, part0 = _pass_state()
    first_id = trace.next_span_id()
    t0 = time.perf_counter()
    run()
    wall = time.perf_counter() - t0
    assert trace.next_span_id() == first_id + 1, \
        "a pass allocated a Span with the ring off"
    assert trace.spans() == []
    whole1, part1 = _pass_state()
    for k in PASSES:
        rose = 1 if k == kind else 0
        assert whole1[k][0] - whole0[k][0] == rose, (k, kind)
        for p in ("fill", "drain"):
            assert part1[k, p][0] - part0[k, p][0] == rose, (k, p, kind)
    took = whole1[kind][1] - whole0[kind][1]
    fill = part1[kind, "fill"][1] - part0[kind, "fill"][1]
    drain = part1[kind, "drain"][1] - part0[kind, "drain"][1]
    assert 0 < fill and 0 < drain
    assert fill + drain <= took <= wall


@pytest.mark.parametrize("kind", PASSES)
def test_a_pass_s_ends_are_spans_under_its_root(kind, tmp_path):
    """With the ring on: `fleet.<kind>` keeps its name and tags,
    `fleet.pass.fill` and `fleet.pass.drain` are its children on the
    packing thread, one after the other inside it, and the threads the
    pass starts work inside its wall."""
    run = _run_pass(kind, _volumes(tmp_path), "numpy")
    trace.enable()
    trace.clear()
    try:
        run()
        spans = trace.spans()
    finally:
        trace.disable()
        trace.clear()
    # (a verify's lane closures are spans `fleet.verify` too, stage
    # `verify`: the root is the one that says how many volumes)
    (root,) = [s for s in spans
               if s.name == "fleet." + kind and "volumes" in s.tags]
    (fill,) = [s for s in spans if s.name == "fleet.pass.fill"]
    (drain,) = [s for s in spans if s.name == "fleet.pass.drain"]
    assert root.tags["volumes"] == 2 and root.tags["backend"] == "numpy"
    assert root.tid == threading.get_ident()
    for part in (fill, drain):
        assert part.parent_id == root.id and part.tid == root.tid
    end = root.t0 + root.dur
    assert root.t0 <= fill.t0 and fill.t0 + fill.dur <= drain.t0
    assert drain.t0 + drain.dur <= end
    # no dispatch before the fill is over, none after the drain began
    for s in spans:
        if s.name == "fleet.dispatch":
            assert fill.t0 + fill.dur <= s.t0
            assert s.t0 + s.dur <= drain.t0
    # reader, retire and lane threads live inside the pass's wall
    staged = [s for s in spans
              if s.name in ("fleet.read", "fleet.retire", "fleet.write")
              and "setup" not in s.tags]
    assert {s.name for s in staged} == {"fleet.read", "fleet.retire",
                                        "fleet.write"}
    for s in staged:
        assert root.t0 <= s.t0 and s.t0 + s.dur <= end, s.name


@pytest.fixture()
def store(tmp_path):
    s = Store([str(tmp_path / "d1")], ip="127.0.0.1", port=8080)
    yield s
    s.close()


def _steps():
    return {s: (c.count, c.total) for s, c in
            ((s, StoreEcSecondsHistogram.labels(s))
             for s in ("freeze", "generate", "generate_batch", "write_ecx",
                       "locate", "rebuild_batch"))}


@pytest.mark.parametrize("call, kind, holds, outside", [
    ("generate", "encode", "generate_batch", ("freeze", "write_ecx")),
    ("rebuild", "rebuild", "rebuild_batch", ("locate",)),
])
def test_the_store_s_steps_and_the_pass_fit_in_the_call(store, call, kind,
                                                        holds, outside):
    """A store EC call by step: the steps outside the pass and the pass
    are each observed once, lie side by side (their sum fits the call's
    wall), and the step that holds the pass is no shorter than it."""
    for vid in (1, 2):
        fill_volume(store, vid)
    if call == "rebuild":
        bases = store_ec.generate_ec_shards_batch(store, [1, 2],
                                                  backend="numpy")
        for base in bases.values():
            os.remove(shard_file_name(base, 11))
    steps0, (whole0, _) = _steps(), _pass_state()
    t0 = time.perf_counter()
    if call == "generate":
        store_ec.generate_ec_shards_batch(store, [1, 2], backend="numpy")
    else:
        assert store_ec.rebuild_ec_shards_batch(
            store, [1, 2], backend="numpy") == {1: [11], 2: [11]}
    wall = time.perf_counter() - t0
    steps1, (whole1, _) = _steps(), _pass_state()
    rose = {s: steps1[s][0] - steps0[s][0] for s in steps0}
    assert rose == {s: int(s == holds or s in outside) for s in steps0}
    assert whole1[kind][0] - whole0[kind][0] == 1
    took = whole1[kind][1] - whole0[kind][1]
    beside = sum(steps1[s][1] - steps0[s][1] for s in outside)
    assert took <= steps1[holds][1] - steps0[holds][1]
    assert 0 < beside and beside + took <= wall


def test_the_one_volume_generate_is_timed_too(store):
    fill_volume(store, 1)
    steps0 = _steps()
    store_ec.generate_ec_shards(store, 1, backend="numpy")
    steps1 = _steps()
    assert {s for s in steps0 if steps1[s][0] > steps0[s][0]} == \
        {"freeze", "generate"}


# -- every series a metric file names is one the program exposes --------------

_LAYER_METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "layer_metrics")
_SERIES = re.compile(r"^(?P<family>\w+?)(?P<suffix>_sum|_count|_bucket)?"
                     r"(?:\{(?P<labels>.*)\})?$")


def _exposed(series: str, printed: set) -> bool:
    """Whether `/metrics` has (`printed`: the keys of its lines), or at
    the family's first observation will have, a line under exactly this
    key — what `benchmark/readers/prom_delta.py` looks up. A labelled
    series is printed from the moment its child is resolved; a family
    without labels from its first observation on, so there the family
    itself has to be registered, label-less, and of the kind the suffix
    says."""
    m = _SERIES.match(series)
    if m is None:
        return False
    if m["labels"] is not None:
        return series in printed
    family = REGISTRY._metrics.get(m["family"] if m["suffix"] else series)
    return family is not None and not family.label_names and \
        (family.kind == "histogram") == bool(m["suffix"])


def _families_in_place() -> None:
    """The modules whose import resolves the children the benchmark's
    cells read; the unary RPCs' request histogram is resolved when a
    server's handlers are wrapped, which needs no running server."""
    from seaweedfs_tpu import rpc
    from seaweedfs_tpu.ops import rs_kernel  # noqa: F401
    from seaweedfs_tpu.pb import volume_server_pb2
    from seaweedfs_tpu.reads import decode_fleet  # noqa: F401
    from seaweedfs_tpu.scrub import daemon, scanner  # noqa: F401
    from seaweedfs_tpu.server.volume import VolumeServer
    rpc.generic_handler(volume_server_pb2, "VolumeServer", VolumeServer)


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(_LAYER_METRICS, "*.json"))),
    ids=lambda p: os.path.basename(p)[:-len(".json")])
def test_every_series_a_metric_file_reads_is_exposed(path):
    """`prom_delta` reads a series that is not there as 0 — "better" for
    a time — so a refactor that drops or renames a counter would go
    unseen: each series a metric file names (`sum_of`, `per.sum_of`)
    must be on `/metrics` once the program's modules are imported,
    before any work is done. Files of other readers name none."""
    with open(path) as f:
        spec = json.load(f)
    assert spec["name"] == os.path.basename(path)[:-len(".json")]
    args = spec.get("args", {})
    named = list(args.get("sum_of", [])) + \
        list(args.get("per", {}).get("sum_of", []))
    if spec["reader"] != "prom_delta":
        assert not named
        return
    assert named, "a prom_delta metric that names no series"
    _families_in_place()
    printed = {line.rpartition(" ")[0]
               for line in REGISTRY.render().splitlines()
               if line and line[0] != "#"}
    for series in named:
        assert _exposed(series, printed), \
            f"{spec['name']}: no series {series} on /metrics"
