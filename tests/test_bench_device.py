"""bench.py and the compile cache name the device they really have.

PR 22: the peaks table is keyed by the `device_kind` string the device
reports (the attached v5e says "TPU v5 lite"), an unknown kind is an
error and never a default, the device phases fail without a chip, a
failed phase fails the run, and the persistent compile cache has one
owner with one placement rule.
"""

import json
import os

import pytest

import bench
from seaweedfs_tpu.util import compile_cache


def test_the_attached_chips_reported_kind_resolves_to_819():
    assert bench.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    assert bench.device_peaks("TPU v5 lite")["int8_tops"] == 393.0


@pytest.mark.parametrize("kind", ["TPU v5e", "v5litepod", "tpuv5lite",
                                  "TPU v5", "cpu", ""])
def test_an_unknown_device_kind_is_an_error_not_a_default(kind):
    """Names written from memory are not keys: nothing is matched by
    substring, nothing gets the largest bound in the table."""
    with pytest.raises(bench.UnknownDeviceKind) as ei:
        bench.device_peaks(kind)
    assert repr(kind) in str(ei.value)


def test_device_phase_fails_without_a_chip():
    """On the CPU the chained-kernel phase exits instead of printing a
    CPU number under a device metric's name."""
    enc_m, _ = bench._matrices()
    with pytest.raises(SystemExit) as ei:
        bench.tpu_phase_gbps(enc_m)
    assert "no accelerator" in str(ei.value)
    with pytest.raises(SystemExit, match="no accelerator"):
        bench.require_accelerator()


def test_a_failed_phase_fails_the_run(monkeypatch, capsys):
    """The fleet sweep's exception used to be printed as a metric line
    and the run exited 0; now it propagates."""
    device = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "require_accelerator", lambda: device)
    monkeypatch.setattr(bench, "_cpu_backend", lambda: "native")
    monkeypatch.setattr(bench, "cpu_phase_gbps", lambda m, b: 1.0)
    monkeypatch.setattr(bench, "tpu_phase_gbps", lambda m: 10.0)

    def broken():
        raise RuntimeError("sweep broke")

    monkeypatch.setattr(bench, "fleet_batch_sweep", broken)
    with pytest.raises(RuntimeError, match="sweep broke"):
        bench.main()
    headline = json.loads(capsys.readouterr().out.splitlines()[0])
    # every result line names the device it ran on
    assert headline["device"] == device
    assert headline["metric"] == "ec_encode_rebuild_gbps"


def test_compile_cache_placement_rule():
    d = compile_cache.DEFAULT_DIR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # one fixed directory inside the checkout, ignored by git
    assert d == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()
    # env var set: cache there, NO path set in code
    assert compile_cache.placement("tpu,cpu", "/x/cache") == \
        ("/x/cache", False)
    assert compile_cache.placement(None, "/x/cache") == ("/x/cache", False)
    # unset: the one fixed directory, set in code
    assert compile_cache.placement("tpu,cpu", None) == (d, True)
    assert compile_cache.placement(None, "") == (d, True)
    # held to the CPU (this suite): no cache at all
    assert compile_cache.placement("cpu", "/x/cache") == ("", False)
    assert compile_cache.configure() == ""


def test_no_code_path_derives_the_cache_directory_from_a_moving_name():
    """The directory is part of the cache key: never a tempfile, a pid
    or a time. And the program sets the path in exactly one place."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        ["grep", "-rlE", "jax_compilation_cache_dir|compilation_cache_dir",
         "--include=*.py", "seaweedfs_tpu", "bench.py", "bench_configs.py",
         "bench_profile.py", "chip_smoke.py", "__graft_entry__.py"],
        cwd=repo, capture_output=True, text=True).stdout.split()
    assert out == ["seaweedfs_tpu/util/compile_cache.py"], out
