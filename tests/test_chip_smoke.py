"""chip_smoke.py's control flow, on the CPU (tier-1).

The smoke itself only counts on the machine with the chip; what can be
held here is that its phases run end to end at the rehearsal's tiny
size, that it refuses to start without a TPU, that a failing phase
stops the run and is named, and that none of those ways ever prints
the contract line. Each case starts the script the way the driver does
— a process of its own (held to the CPU, like every child the suite
starts) — because the script owns its process: it picks the platform
before JAX is imported.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
PHASES = ["found", "native", "cluster", "load", "reference", "encode",
          "ec_read", "degrade", "degraded_read", "rebuild", "scrub",
          "stop"]


def _run(tmp_path, *args, env=None, timeout=300):
    """Run a COPY of the script's checkout view: the script keeps its
    data beside itself, so concurrent cases (and a builder's own run)
    must not share one directory. Symlinks keep the copy free."""
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("chip_smoke.py", "seaweedfs_tpu", "tests"):
        os.symlink(os.path.join(REPO, name), root / name)
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("XLA_FLAGS", "SEAWEED_FAILPOINTS", "SEAWEED_SCHED",
              "SEAWEED_SANITIZE"):
        e.pop(k, None)
    e.update(env or {})
    r = subprocess.run([sys.executable, str(root / "chip_smoke.py"), *args],
                       cwd=str(root), env=e, capture_output=True,
                       text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    return r, lines


def _contract_lines(lines):
    return [ln for ln in lines if "device" in ln and "phase" not in ln]


def test_rehearsal_runs_every_phase_and_never_prints_the_contract_line(
        tmp_path):
    r, lines = _run(tmp_path, "--rehearse", "--seed", "3")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert [ln.get("phase") for ln in lines[:-1]] == PHASES
    assert all(ln["ok"] for ln in lines[1:-1])
    found = lines[0]
    assert found["platform"] == "cpu" and found["rehearsal"] is True
    assert found["seed"] == 3 and found["volumes"] == 2
    by = {ln["phase"]: ln for ln in lines[:-1]}
    # the grouped route ran, on the jax backend, and its kernels' input
    # was placed on the (only) device
    assert by["cluster"]["backend"] == "jax"
    assert by["encode"]["route"].startswith("generate_ec_shards_batch")
    assert by["encode"]["spans"]["fleet.dispatch"] >= 1
    for phase in ("encode", "degraded_read", "rebuild", "scrub"):
        assert by[phase]["device_bytes"].get("cpu:0", 0) > 0, by[phase]
    assert by["degraded_read"]["degraded_intervals"] > 0
    assert by["scrub"]["found"] == 0 and by["scrub"]["stripes"] > 0
    # the last line says rehearsal; nothing looks like the contract line
    assert lines[-1] == {"ok": True, "rehearsal": True,
                         "note": lines[-1]["note"]}
    assert not _contract_lines(lines)
    assert r.stdout.rstrip().splitlines()[-1] == json.dumps(lines[-1])
    assert not (tmp_path / "checkout" / "chip_smoke_data").exists()


def test_without_a_chip_the_smoke_refuses_to_start(tmp_path):
    r, lines = _run(tmp_path)
    assert r.returncode != 0
    assert "chip_smoke: no chip" in r.stdout
    assert [ln.get("phase") for ln in lines] == ["found"]
    assert lines[0]["platform"] == "cpu"
    assert not _contract_lines(lines)
    assert '"ok": true' not in r.stdout


def test_a_failing_phase_stops_the_run_and_is_named(tmp_path):
    """The fleet's dispatch failpoint (an existing seam, armed from the
    environment) breaks the encode: exit 1, the phase's traceback and
    name, no later phase, no last `ok` line."""
    r, lines = _run(tmp_path, "--rehearse",
                    env={"SEAWEED_FAILPOINTS": "fleet.dispatch=error"})
    assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
    phases = [ln.get("phase") for ln in lines]
    assert phases == PHASES[:PHASES.index("encode") + 1]
    assert lines[-1]["phase"] == "encode" and lines[-1]["ok"] is False
    assert "chip_smoke: FAILED in phase encode" in r.stdout
    assert "Traceback" in r.stderr
    assert not _contract_lines(lines)
    assert not any(ln.get("ok") is True and "phase" not in ln
                   for ln in lines)
    assert not (tmp_path / "checkout" / "chip_smoke_data").exists()


def test_four_chip_rehearsal_runs_only_the_mesh_path(tmp_path):
    r, lines = _run(tmp_path, "--rehearse", "--chips", "4")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "found", "native", "cluster", "load", "reference",
        "one_device_fleet", "mesh_encode", "mesh_verify", "stop"]
    by = {ln["phase"]: ln for ln in lines[:-1]}
    assert by["found"]["count"] == 4 and by["cluster"]["ec_mesh"] is True
    for phase in ("mesh_encode", "mesh_verify"):
        assert by[phase]["mesh_fallbacks"] == 0
        assert by[phase]["mesh_buckets"] > 0
        placed = by[phase]["device_bytes"]
        assert sorted(placed) == [f"cpu:{i}" for i in range(4)], placed
        assert all(v > 0 for v in placed.values())
    assert by["mesh_encode"]["compared_bytes_one_device"] == \
        by["mesh_encode"]["compared_bytes"] > 0
    assert lines[-1].get("rehearsal") is True
    assert not _contract_lines(lines)
