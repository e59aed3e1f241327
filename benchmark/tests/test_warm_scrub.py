"""Tests of the scrub cell (``warm-scrub.scrub``), on the CPU, beside
``test_benchmark.py``, whose lists of cells take the cell from the
manifest (files found by name, rehearsal with and without a trace) and
whose lists of controls and faults a new cell cannot join: the control
comes out not correct, and so does a program whose scrub reports clean
without reading.
"""

from __future__ import annotations

import json
import os

import pytest

from test_benchmark import BENCH, last_json, manifest, run_py

from benchmark import work
from benchmark.readers import prom_delta

CELL = "warm-scrub.scrub"


def test_the_cell_is_in_the_manifest_with_its_layer_metrics():
    m = manifest()
    assert CELL in [w["name"] for w in m["workloads"]]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("warm-scrub", "scrub", 1)
    mine = {p["name"] for p in m["per_layer"]
            if CELL in p.get("workloads", [CELL])}
    assert {"rs_verify_roofline", "fleet_verify_on_device_share",
            "scrub_scan_s_per_gib", "scrub_verify_s_per_gib",
            "scrub_repair_s_per_gib", "scrub_round_s",
            "device_idle_pct.job", "rs_fetch_s_per_gib",
            "fleet_staging_reuse_share"} <= mine
    # the other passes' kernels, and a verify takes no result memory
    assert not {"rs_encode_roofline", "rs_rebuild_roofline",
                "fleet_rebuild_volumes_per_group",
                "rs_result_lent_share"} & mine
    assert all("workloads" in p for p in m["per_layer"])


def would_be(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    return last_json(proc)["would_be"]


def test_the_rehearsal_holds_every_round_to_its_report():
    p = run_py("--workload", CELL, "--seed", "2147483659", "--seconds", "3",
               "--trace", "1", "--rehearse")
    would = would_be(p)
    assert would["correct"] is True and would["failed"] == 0
    assert set(would["compared"]) == {
        "shard_bytes_differing", "shard_files_missing",
        "dat_needles_differing", "sectors_unrepaired", "damage_misreported"}
    assert all(c == {"value": 0, "limit": 0}
               for c in would["compared"].values())
    m = would["metrics"]
    assert m["fleet_verify_on_device_share"]["value"] == 1.0
    assert m["scrub_round_s"]["value"] > 0
    for name in ("scrub_scan_s_per_gib", "scrub_verify_s_per_gib",
                 "scrub_repair_s_per_gib"):
        assert m[name]["value"] > 0
    # counts come back, not parity
    assert m["rs_fetch_s_per_gib"]["value"] < m["rs_place_s_per_gib"]["value"]
    rounds = next(json.loads(ln)["window"]["rounds"]
                  for ln in p.stdout.splitlines()
                  if ln.startswith('{"window"'))
    assert len(rounds) >= 2 and would["attempted"] == 4 * len(rounds)
    # data and parity shards in turn, volume after volume
    assert [r["shard"] for r in rounds[:2]] == [3, 11]
    assert len({r["volume"] for r in rounds[:4]}) == min(4, len(rounds))


def test_the_control_comes_out_not_correct():
    would = would_be(run_py(
        "--workload", CELL, "--seed", "77", "--seconds", "2", "--trace", "0",
        "--rehearse", "--control", "missed-sector"))
    assert would["correct"] is False and would["control"] == "missed-sector"
    c = would["compared"]
    assert c["sectors_unrepaired"]["value"] == 1
    assert c["shard_bytes_differing"]["value"] == 4096
    # every round's report was right, the files and the .dat all there
    assert c["damage_misreported"]["value"] == 0
    assert c["shard_files_missing"]["value"] == 0
    assert c["dat_needles_differing"]["value"] == 0


def test_a_scrub_that_reports_clean_without_reading_comes_out_not_correct():
    p = run_py("--workload", CELL, "--seed", "78", "--seconds", "2",
               "--trace", "0",
               script=os.path.join(BENCH, "tests", "broken_scrub.py"))
    would = would_be(p)
    assert would["correct"] is False
    c = would["compared"]
    rounds = would["attempted"] // 4
    assert rounds >= 1 and would["failed"] == would["attempted"]
    # the untimed pass of set-up and every round of the window
    assert c["sectors_unrepaired"]["value"] == rounds + 1
    assert c["damage_misreported"]["value"] == rounds + 1
    assert c["shard_bytes_differing"]["value"] == 4096 * (rounds + 1)


def test_the_verify_work_count():
    """A round as .dat bytes of a [4, 10] map: 1.4 bytes and 512 int8
    operations a byte; the [1, 10] rebuild of one shard moves 11/14 of
    what a verify of its volume moves."""
    d = 1 << 30
    assert work.gf_linear_map(d / 10, 10, 4) == {
        "bytes": pytest.approx(1.4 * d), "ops": pytest.approx(512 * d)}
    assert work.gf_linear_map(d / 10, 10, 1)["bytes"] == \
        pytest.approx(11 / 14 * work.gf_linear_map(d / 10, 10, 4)["bytes"])


@pytest.mark.parametrize("metric", ["fleet_verify_on_device_share",
                                    "scrub_verify_s_per_gib"])
def test_the_new_counters_read_nothing_or_zero_on_a_program_without_them(
        metric):
    """The parent commit of the PR that brought the cell has neither
    series: a ratio of two absent series reads nothing, a sum over a
    quantity of the driver's reads 0; neither raises."""
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        args = json.load(f)["args"]
    ctx = {"metrics0": {"x": 1.0}, "metrics1": {"x": 2.0},
           "work": {"gib_done": 4.0}}
    assert prom_delta.read(ctx, args) == \
        (None if metric == "fleet_verify_on_device_share" else 0.0)
