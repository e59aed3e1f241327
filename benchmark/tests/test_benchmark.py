"""Tests of the benchmark itself, on the CPU: ``pytest benchmark/tests``.

They hold the manifest to the contract's names, find every file a cell
needs by name, run each cell as a rehearsal in a process of its own
(the cells of the manifest from this checkout; the cells that wait
under ``benchmark/waiting/`` from a copy whose manifest has their
entries merged in, which is how a later PR adds them),
check the reference against the program's host arithmetic, the work
counts and the trace reduction on hand-made data, show that a cell, a
traffic mix and a per-layer metric are added as files only, and keep
the controls and the planted faults: each has to come out not correct.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import reference, trace_reduce, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def waiting() -> list:
    """The cells kept as files but not in the manifest: each file holds
    the manifest entries that bring one."""
    d = os.path.join(BENCH, "waiting")
    out = []
    for name in sorted(os.listdir(d)):
        with open(os.path.join(d, name)) as f:
            out.append(json.load(f))
    return out


def merged_manifest() -> dict:
    m = manifest()
    for w in waiting():
        for kind in ("configs", "workloads", "end_to_end", "per_layer"):
            m[kind] += w[kind]
    return m


CELLS = [w["name"] for w in manifest()["workloads"]]
WAITING_CELLS = [w["name"] for w in waiting()]
ALL_CELLS = CELLS + WAITING_CELLS


def copy_of_the_checkout(dst, m: dict) -> str:
    """A checkout with another manifest: the program linked, the
    benchmark copied."""
    for name in ("seaweedfs_tpu", "tests"):
        os.symlink(os.path.join(ROOT, name), os.path.join(dst, name))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return str(dst)


@pytest.fixture(scope="session")
def root_of(tmp_path_factory):
    """cell -> the checkout to run it from."""
    merged = copy_of_the_checkout(tmp_path_factory.mktemp("merged"),
                                  merged_manifest())
    return lambda cell: ROOT if cell in CELLS else merged


def run_py(*args, root=ROOT, script=None, timeout=300):
    cmd = [sys.executable, script or os.path.join(root, "benchmark", "run.py"),
           *args]
    return subprocess.run(cmd, cwd=root, env=ENV, capture_output=True,
                          text=True, timeout=timeout)


def last_json(proc) -> dict:
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


# -- the manifest -------------------------------------------------------------

@pytest.mark.parametrize("which", ["manifest", "with_waiting_cells"])
def test_manifest_keeps_to_the_contract(which):
    m = manifest() if which == "manifest" else merged_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert {c["name"] for c in m["configs"]} == \
        {w["config"] for w in m["workloads"]}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        if which == "manifest" or e["bound"] is not None:
            # a waiting cell's bounds are set by the PR that measures it
            assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in m["workloads"]}
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(e["unit"]) and e["moves"] in e2e
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for c in e.get("workloads", cells):
            assert c in e2e[e["moves"]].get("workloads", cells), \
                f"{e['name']} moves a metric that {c} does not report"
    for w in m["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and w["config"] in \
            {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith(m["paths"][0] + "/")
        assert all(NAME.match(k) for k in c["reduced"])


def test_files_under_paths_have_plain_names():
    for d, _, files in os.walk(BENCH):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell):
    m = merged_manifest()
    w = next(w for w in m["workloads"] if w["name"] == cell)
    cfg = next(c for c in m["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert {"cluster", "rehearsal", "code", "guarantees"} <= set(config)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       mix["driver"] + ".py"))
    mine = [p for p in m["per_layer"] if cell in p.get("workloads", [cell])]
    assert mine
    for p in mine:
        with open(os.path.join(BENCH, "layer_metrics",
                               p["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["layer"] == p["layer"]
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))


def test_every_data_file_belongs_to_a_cell():
    """No configuration, traffic mix, per-layer metric, driver or reader
    lies about that neither the manifest nor a waiting cell names."""
    m = merged_manifest()
    def there(sub):
        return {os.path.splitext(f)[0]
                for f in os.listdir(os.path.join(BENCH, sub))
                if not f.startswith("__")}
    assert {os.path.basename(c["file"])[:-5] for c in m["configs"]} == \
        there("configs")
    assert {w["traffic"] for w in m["workloads"]} == there("traffic")
    assert {p["name"] for p in m["per_layer"]} == there("layer_metrics")
    drivers, readers = set(), set()
    for t in there("traffic"):
        with open(os.path.join(BENCH, "traffic", t + ".json")) as f:
            drivers.add(json.load(f)["driver"])
    for p in there("layer_metrics"):
        with open(os.path.join(BENCH, "layer_metrics", p + ".json")) as f:
            readers.add(json.load(f)["reader"])
    assert drivers | {"http_reads_client"} == there("drivers")
    assert readers == there("readers")


# -- the runner ---------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ALL_CELLS)
def test_rehearsal_runs_and_prints_no_result_line(cell, trace, root_of):
    root = root_of(cell)
    p = run_py("--workload", cell, "--seed", "2147483659", "--seconds", "2",
               "--trace", str(trace), "--rehearse", root=root)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    last = last_json(p)
    assert last.get("rehearsal") is True and "correct" not in last
    would = last["would_be"]
    assert would["correct"] is True and would["failed"] == 0
    assert list(would)[-1] == "compared"
    if trace == 0:
        assert "setup_s" in would["metrics"] and len(would["metrics"]) >= 2
    else:
        # no device in the trace of a CPU run: shares of the chip are
        # left out, never 0; the program's own counters are read
        assert would["metrics"]
        assert not any("roofline" in k or "idle" in k
                       for k in would["metrics"])
    assert not os.path.exists(os.path.join(root, ".bench_data"))


def test_without_a_tpu_the_run_refuses_with_one_line():
    p = run_py("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert p.returncode != 0
    out = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert len(out) == 1 and "no chip" in out[0]


def test_in_a_directory_without_the_program_the_run_refuses(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0", root=str(tmp_path))
    assert p.returncode != 0 and "correct" not in p.stdout
    assert "the program is not here" in p.stdout


CONTROLS = [("warm-seal.encode", "weak-parity"),
            ("node-loss.degraded-read", "no-reconstruct")]


@pytest.mark.parametrize("cell,control", CONTROLS)
def test_the_control_comes_out_not_correct(cell, control, root_of):
    p = run_py("--workload", cell, "--seed", "77", "--seconds", "2",
               "--trace", "0", "--rehearse", "--control", control,
               root=root_of(cell))
    assert p.returncode == 0, p.stderr[-4000:]
    would = last_json(p)["would_be"]
    assert would["correct"] is False and would["control"] == control
    assert any(c["value"] > c["limit"] for c in would["compared"].values())


FAULTS = [("warm-seal.encode", "output-byte-altered", "shard_bytes_differing"),
          ("warm-seal.encode", "parity-not-written", "shard_bytes_differing"),
          ("warm-seal.encode", "stored-byte-altered", "dat_needles_differing"),
          ("node-loss.degraded-read", "output-byte-altered",
           "reads_unanswered"),
          ("node-loss.degraded-read", "answer-altered", "reads_wrong_bytes")]


@pytest.mark.parametrize("cell,fault,caught_by", FAULTS)
def test_a_broken_timed_path_comes_out_not_correct(cell, fault, caught_by,
                                                   root_of):
    root = root_of(cell)
    p = run_py("--fault", fault, "--workload", cell, "--seed", "78",
               "--seconds", "2", "--trace", "0", root=root,
               script=os.path.join(root, "benchmark", "tests",
                                   "broken_run.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    would = last_json(p)["would_be"]
    assert would["correct"] is False
    c = would["compared"][caught_by]
    assert c["value"] > c["limit"]


# -- adding a cell is adding files --------------------------------------------

def test_a_cell_a_mix_and_a_layer_metric_are_added_as_files_only(tmp_path):
    m = manifest()
    copy_of_the_checkout(tmp_path, m)
    with open(os.path.join(BENCH, "traffic", "encode.json")) as f:
        mix = json.load(f)
    mix.update(name="encode-three", batch_volumes=3)
    with open(tmp_path / "benchmark" / "traffic" / "encode-three.json",
              "w") as f:
        json.dump(mix, f)
    with open(tmp_path / "benchmark" / "layer_metrics" /
              "fleet_dispatch_s_per_gib.json", "w") as f:
        json.dump({"name": "fleet_dispatch_s_per_gib", "layer": "schedulers",
                   "reader": "prom_delta", "args": {
                       "sum_of": ['SeaweedFS_fleet_stage_seconds_sum'
                                  '{stage="dispatch"}'],
                       "per": {"work": "gib_done"}}}, f)
    m["workloads"].append({"name": "warm-seal.encode-three",
                           "config": "warm-seal", "traffic": "encode-three",
                           "chips": 1, "why": "three volumes a command"})
    for e in m["end_to_end"]:
        if e["name"] == "ec_job_mbps":
            e["workloads"].append("warm-seal.encode-three")
    m["per_layer"].append({
        "name": "fleet_dispatch_s_per_gib", "unit": "s/GiB",
        "better": "lower", "source": "program_counter",
        "layer": "schedulers", "moves": "ec_job_mbps",
        "workloads": ["warm-seal.encode-three"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    p = run_py("--workload", "warm-seal.encode-three", "--seed", "5",
               "--seconds", "1", "--trace", "1", "--rehearse",
               root=str(tmp_path))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    would = last_json(p)["would_be"]
    assert would["attempted"] == 3 and would["correct"] is True
    assert would["metrics"]["fleet_dispatch_s_per_gib"]["value"] > 0


def test_a_shape_that_warm_up_misses_gives_no_result(tmp_path):
    """A program compiled inside the window: the run says so and prints
    no result line, rehearsal or not."""
    copy_of_the_checkout(tmp_path, manifest())
    path = tmp_path / "benchmark" / "traffic" / "encode.json"
    with open(path) as f:
        mix = json.load(f)
    mix["warm_up"]["lane_widths_log2"] = [16, 16]
    with open(path, "w") as f:
        json.dump(mix, f)
    p = run_py("--workload", "warm-seal.encode", "--seed", "6",
               "--seconds", "1", "--trace", "0", "--rehearse",
               root=str(tmp_path))
    assert p.returncode != 0
    assert "compiled inside the window" in p.stdout
    assert "would_be" not in p.stdout and '"correct"' not in p.stdout


# -- the yardstick ------------------------------------------------------------

def test_reference_agrees_with_the_programs_host_arithmetic():
    from seaweedfs_tpu.ops import gf256
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    assert np.array_equal(reference.coding_matrix(),
                          gf256.rs_coding_matrix(10, 14))
    want = gf256.gf_linear_numpy(gf256.rs_coding_matrix(10, 14)[10:], data)
    assert np.array_equal(reference.apply(reference.parity_matrix(), data),
                          want)
    broken = reference.parity_matrix().copy()
    broken[2, 5] ^= 1
    assert not np.array_equal(reference.apply(broken, data), want)


def test_reference_reconstructs_lost_shards():
    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, (10, 1000), dtype=np.uint8)
    full = reference.apply(reference.coding_matrix(), data)
    present = [1, 2, 4, 5, 6, 7, 8, 9, 10, 11]
    got = reference.apply(reference.decode_matrix(present, [0, 3]),
                          full[present])
    assert np.array_equal(got, full[[0, 3]])


def test_reference_striping_and_locate(tmp_path):
    rng = np.random.default_rng(13)
    large, small = 4096, 256
    dat = rng.integers(0, 256, 10 * large + 7 * small + 13, dtype=np.uint8)
    path = str(tmp_path / "1.dat")
    dat.tofile(path)
    shards = [str(tmp_path / f"1.ec{i:02d}") for i in range(14)]
    reference.write_shards(path, shards, large, small)
    n_large, n_small, size = reference.layout(dat.size, large, small)
    assert (n_large, n_small, size) == (1, 1, large + small)
    assert all(os.path.getsize(s) == size for s in shards)
    # any range of the .dat reads back from the data shards by locate
    for off, n in [(0, 10), (large - 3, 9), (10 * large - 5, 300),
                   (10 * large + 5 * small, 2 * small + 13)]:
        got = b""
        for sid, at, k in reference.locate(dat.size, large, small, off, n):
            with open(shards[sid], "rb") as f:
                f.seek(at)
                got += f.read(k)
        assert got == dat[off:off + n].tobytes()
    same = reference.compare_volume(path, shards, large, small, threads=2)
    assert same == {"differing": 0, "compared": 14 * size}
    with open(shards[12], "r+b") as f:
        f.seek(100)
        was = f.read(1)[0]
        f.seek(100)
        f.write(bytes([was ^ 1]))
    assert reference.compare_volume(path, shards, large, small)[
        "differing"] >= 1
    os.remove(shards[3])
    assert reference.compare_volume(path, shards, large, small)[
        "differing"] >= size


def test_work_counts():
    d = 1 << 30
    enc = work.gf_linear_map(d / 10, 10, 4)
    assert enc == {"bytes": pytest.approx(1.4 * d),
                   "ops": pytest.approx(512 * d)}
    b = 12345
    dec = work.gf_linear_map(b, 10, 1)
    assert dec == {"bytes": 11 * b, "ops": 1280 * b}
    peak = work.peaks("TPU v5 lite")
    least = work.least_seconds(enc, peak)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(1.4 * d / 819e9)
    with pytest.raises(work.UnknownDeviceKind):
        work.peaks("TPU v9 imaginary")


def test_trace_reduction_on_a_hand_made_list():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("a", 3.0, 1.0), ("c", 9.5, 2.0)]
    busy, merged = trace_reduce.busy_union(ev)
    assert busy == pytest.approx(4.5)
    assert merged == [(0.0, 1.5), (3.0, 4.0), (9.5, 11.5)]
    r = trace_reduce.reduce({"/device:TPU:0": ev}, 0.0, 10.0,
                            host_spans=[("outer", 0.0, 10.0),
                                        ("fetch", 1.5, 1.5),
                                        ("write", 4.0, 5.0)])
    assert r["busy_s"] == pytest.approx(3.0)        # c is clipped at 10
    assert r["device_op_s"] == pytest.approx(3.5)   # a + b overlap: summed
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["device_ops"][0] == ["a", 2.0]
    # idle: 1.5-3.0 and 4.0-9.5; by span name, overlapping
    assert dict(map(tuple, r["idle_gaps"])) == {
        "outer": pytest.approx(7.0), "fetch": pytest.approx(1.5),
        "write": pytest.approx(5.0)}
    r = trace_reduce.reduce({"/device:TPU:0": ev}, 0.0, 10.0,
                            host_spans=[("fetch", 1.5, 1.0)])
    assert dict(map(tuple, r["idle_gaps"])) == {
        "no span open": pytest.approx(6.0), "fetch": pytest.approx(1.0)}
    with pytest.raises(ValueError):
        trace_reduce.reduce({}, 0.0, 1.0)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    from benchmark.readers import (device_trace, driver_value, prom_delta,
                                   span_stat)
    ctx = {"metrics0": {}, "metrics1": {}, "spans": [], "trace": None,
           "work": {}, "peaks": None, "driver": {"reads_per_s": 80.5}}
    assert driver_value.read(ctx, {"value": "read_p50_ms"}) is None
    assert driver_value.read(ctx, {"value": "reads_per_s"}) == 80.5
    assert device_trace.read(ctx, {"stat": "idle_pct"}) is None
    assert span_stat.read(ctx, {"span": "reads.decode"}) is None
    assert prom_delta.read(ctx, {"sum_of": ["x"],
                                 "per": {"work": "gib_done"}}) is None
    ctx.update(trace={"idle_share": 0.25, "device_op_s": 2.0},
               work={"traced": {"dat_bytes": float(1 << 30)}},
               peaks=work.peaks("TPU v5 lite"))
    assert device_trace.read(ctx, {"stat": "idle_pct"}) == 25.0
    got = device_trace.read(ctx, {"stat": "roofline_pct", "work": {
        "columns_from": "dat_bytes", "columns_per_unit": 0.1,
        "in_rows": 10, "out_rows": 4}})
    assert got == pytest.approx(100 * 1.4 * (1 << 30) / 819e9 / 2.0)
