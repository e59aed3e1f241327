"""Tests of the repair cell (``node-repair.rebuild``), on the CPU, beside
``test_benchmark.py``, whose lists of cells take the cell from the
manifest (files found by name, rehearsal with and without a trace) and
whose lists of controls and faults a new cell cannot join: the control
comes out not correct, a planted fault in the timed path is caught, and
so is one that only the FIRST of several rounds had.
"""

from __future__ import annotations

import json
import os

import pytest

from test_benchmark import BENCH, last_json, manifest, run_py

from benchmark import work
from benchmark.readers import prom_delta

CELL = "node-repair.rebuild"


def test_the_cell_is_in_the_manifest_with_its_layer_metrics():
    m = manifest()
    assert [w["name"] for w in m["workloads"]][-1] == CELL
    assert [c["name"] for c in m["configs"]][-1] == "node-repair"
    mine = {p["name"] for p in m["per_layer"]
            if CELL in p.get("workloads", [CELL])}
    assert {"rs_rebuild_roofline", "fleet_rebuild_volumes_per_group",
            "device_idle_pct.job", "fleet_dispatch_s_per_gib",
            "rs_stage_s_per_gib"} <= mine
    # rebuild reads into no staging buffer: those two stay encode's
    assert not {"fleet_staging_wait_s_per_gib",
                "fleet_staging_reuse_share", "rs_encode_roofline"} & mine


def would_be(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    return last_json(proc)["would_be"]


def test_the_control_comes_out_not_correct():
    would = would_be(run_py(
        "--workload", CELL, "--seed", "77", "--seconds", "2", "--trace", "0",
        "--rehearse", "--control", "weak-decode"))
    assert would["correct"] is False and would["control"] == "weak-decode"
    c = would["compared"]
    assert c["shard_bytes_differing"]["value"] > 0
    # the survivors, the .dat and the number of files are as they were
    assert c["shard_files_missing"]["value"] == 0
    assert c["dat_needles_differing"]["value"] == 0


def rounds_of(proc) -> int:
    for line in proc.stdout.splitlines():
        if line.startswith('{"window"'):
            return len(json.loads(line)["window"]["rounds"])
    raise AssertionError(proc.stdout[-2000:])


@pytest.mark.parametrize("script", ["broken_run.py", "broken_first_round.py"])
def test_an_altered_kernel_result_comes_out_not_correct(script):
    """In every round (`broken_run.py --fault output-byte-altered`), or
    in the first round alone, whose files the second loss deleted from
    their places: `shard_bytes_differing` catches both."""
    fault = ["--fault", "output-byte-altered"] \
        if script == "broken_run.py" else []
    p = run_py(*fault, "--workload", CELL, "--seed", "78", "--seconds", "2",
               "--trace", "0",
               script=os.path.join(BENCH, "tests", script))
    would = would_be(p)
    assert rounds_of(p) >= 2
    assert would["correct"] is False
    c = would["compared"]["shard_bytes_differing"]
    assert c["value"] > c["limit"]
    if script == "broken_first_round.py":
        # one byte a dispatch, and the later rounds' dispatches are right
        assert c["value"] < rounds_of(p)


def test_the_rebuild_work_count():
    d = 1 << 30
    assert work.gf_linear_map(d / 10, 10, 2) == {
        "bytes": pytest.approx(1.2 * d), "ops": pytest.approx(256 * d)}


def test_volumes_per_group_reads_nothing_on_a_program_without_the_counters():
    """The parent commit of the PR that brought the cell has neither
    series: the reader returns nothing and does not raise."""
    with open(os.path.join(BENCH, "layer_metrics",
                           "fleet_rebuild_volumes_per_group.json")) as f:
        args = json.load(f)["args"]
    ctx = {"metrics0": {"x": 1.0}, "metrics1": {"x": 2.0}, "work": {}}
    assert prom_delta.read(ctx, args) is None
    ctx["metrics1"].update(SeaweedFS_fleet_rebuild_volumes_total=12.0,
                           SeaweedFS_fleet_rebuild_groups_total=6.0)
    assert prom_delta.read(ctx, args) == 2.0
