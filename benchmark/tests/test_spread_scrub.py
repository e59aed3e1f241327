"""Tests of the spread scrub cell (``spread-scrub.scrub``), on the CPU,
beside ``test_warm_scrub.py``: the cell is in the manifest with its
metrics, its rehearsal holds every round to the report of all four
servers and to the layout, and its control comes out not correct.
"""

from __future__ import annotations

import json
import os

import pytest

from test_benchmark import BENCH, last_json, manifest, run_py

from benchmark.readers import prom_delta

CELL = "spread-scrub.scrub"
NEW = ("fleet_verify_remote_share", "fleet_remote_read_s_per_gib")
COMPARED = {"shard_bytes_differing", "shard_files_missing",
            "dat_needles_differing", "sectors_unrepaired",
            "damage_misreported", "needles_unchecked", "stripes_unverified",
            "layout_moved"}


def test_the_cell_is_in_the_manifest_beside_warm_scrub():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("spread-scrub", "scrub-spread", 1)
    e2e = next(e for e in m["end_to_end"] if e["name"] == "ec_job_mbps")
    assert CELL in e2e["workloads"]
    # every layer metric of the all-local cell, and the two that read
    # the remote rows, which only this cell has
    theirs = {p["name"] for p in m["per_layer"]
              if "warm-scrub.scrub" in p["workloads"]}
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert mine == theirs | set(NEW)
    assert all(p["workloads"] == [CELL] for p in m["per_layer"]
               if p["name"] in NEW)
    with open(os.path.join(BENCH, "configs", "spread-scrub.json")) as f:
        cfg = json.load(f)
    assert (cfg["volume_servers"], cfg["cluster"]["n_volume_servers"],
            cfg["join_volume_servers"]) == (4, 1, 3)
    assert all(k + "_why" in cfg["assumed"] for k in cfg["assumed"]
               if not k.endswith("_why"))


def would_be(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    return last_json(proc)["would_be"]


def test_the_rehearsal_holds_every_round_to_four_reports_and_the_layout():
    p = run_py("--workload", CELL, "--seed", "2147483659", "--seconds", "3",
               "--trace", "1", "--rehearse")
    would = would_be(p)
    assert would["correct"] is True and would["failed"] == 0
    assert set(would["compared"]) == COMPARED
    assert all(c == {"value": 0, "limit": 0}
               for c in would["compared"].values())
    m = would["metrics"]
    # 10 or 11 of every 14 rows pulled: each volume is verified by a
    # holder of 4 or 3 of its shards
    assert 10 / 14 - 1e-9 <= m["fleet_verify_remote_share"]["value"] \
        <= 11 / 14 + 1e-9
    assert m["fleet_remote_read_s_per_gib"]["value"] > 0
    assert m["fleet_verify_on_device_share"]["value"] == 1.0
    assert m["scrub_needle_staged_share"]["value"] == 1.0
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    layout = next(ln["layout"] for ln in lines if "layout" in ln)
    # as ec.balance keeps them: 3-4 of a volume's 14 shards a server
    assert all(sorted(len(s) for s in servers.values()) == [3, 3, 4, 4]
               for servers in layout.values())
    rounds = next(ln["window"]["rounds"] for ln in lines if "window" in ln)
    assert len(rounds) >= 2 and would["attempted"] == 4 * len(rounds)
    assert [r["shard"] for r in rounds[:2]] == [3, 11]
    assert {r["damaged_on"] for r in rounds} <= {"verifier", "remote"}
    # the work spread as over a deployment's many volumes: each of the
    # four servers verifies one volume
    assert all(sorted(map(len, r["verified_by"].values())) == [1] * 4
               for r in rounds)


def test_the_control_comes_out_not_correct():
    would = would_be(run_py(
        "--workload", CELL, "--seed", "77", "--seconds", "2", "--trace", "0",
        "--rehearse", "--control", "deferred-lost"))
    assert would["correct"] is False and would["control"] == "deferred-lost"
    c = would["compared"]
    rounds = would["attempted"] // 4 + 1          # and the untimed one
    assert c["stripes_unverified"]["value"] == 4 * rounds
    assert c["sectors_unrepaired"]["value"] == rounds
    assert c["damage_misreported"]["value"] == rounds
    assert c["needles_unchecked"]["value"] > 0
    assert c["layout_moved"]["value"] == 0


@pytest.mark.parametrize("metric", NEW)
def test_the_new_counters_read_nothing_or_zero_on_a_program_without_them(
        metric):
    """The parent commit of the PR that brought the cell has neither
    series (and cannot run the cell): a ratio of two absent series reads
    nothing, a sum over a quantity of the driver's reads 0."""
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        args = json.load(f)["args"]
    ctx = {"metrics0": {"x": 1.0}, "metrics1": {"x": 2.0},
           "work": {"gib_done": 4.0}}
    assert prom_delta.read(ctx, args) == \
        (None if metric == "fleet_verify_remote_share" else 0.0)
