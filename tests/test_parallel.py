"""Mesh-sharded EC pipeline on the virtual 8-device CPU mesh."""

import os

import numpy as np
import pytest

import jax

from seaweedfs_tpu.ops.rs_code import ReedSolomon, DATA_SHARDS
from seaweedfs_tpu.parallel import (
    make_mesh, sharded_encode, ec_pipeline_step, rotate_shards,
)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest should give 8 virtual devices"
    return make_mesh(8)


def test_mesh_factoring(mesh):
    assert mesh.shape["dp"] * mesh.shape["sp"] == 8
    assert mesh.shape["sp"] >= 2  # lanes actually split


def test_sharded_encode_matches_host(mesh):
    rng = np.random.default_rng(0)
    b = mesh.shape["dp"] * 2
    n = mesh.shape["sp"] * 256
    data = rng.integers(0, 256, size=(b, DATA_SHARDS, n), dtype=np.uint8)
    got = np.asarray(sharded_encode(mesh, data))
    want = ReedSolomon(backend="numpy").encode(data)
    np.testing.assert_array_equal(got, want)


def test_pipeline_step_rebuilds_exactly(mesh):
    rng = np.random.default_rng(1)
    b = mesh.shape["dp"]
    n = mesh.shape["sp"] * 128
    data = rng.integers(0, 256, size=(b, DATA_SHARDS, n), dtype=np.uint8)
    parity, rebuilt, mismatches = ec_pipeline_step(mesh, data, drop=(3, 11))
    assert int(mismatches) == 0
    want = ReedSolomon(backend="numpy").encode(data)
    np.testing.assert_array_equal(np.asarray(parity), want)


def _host_rs():
    """Independent host-side comparator: native AVX2 if built, numpy
    otherwise — either way a non-jax implementation of the same code."""
    return ReedSolomon(backend="auto")


def test_pipeline_step_at_64mb_per_device(mesh):
    """Encode + worst-case rebuild at REAL size: >=64MB per device slab
    (round-2 verdict: layout/halo bugs hide at sizes where one tile
    holds everything). Byte-compared against the host backend."""
    rng = np.random.default_rng(7)
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    b = dp
    lanes_per_dev = 6_800_000            # (b/dp)*10*lanes >= 64MB/device
    n = sp * lanes_per_dev
    data = rng.integers(0, 256, size=(b, DATA_SHARDS, n), dtype=np.uint8)
    per_device = (b // dp) * DATA_SHARDS * (n // sp)
    assert per_device >= 64 << 20
    parity, rebuilt, mismatches = ec_pipeline_step(mesh, data, drop=(0, 13))
    assert int(mismatches) == 0
    want = _host_rs().encode(data)
    np.testing.assert_array_equal(np.asarray(parity), want)
    # the rebuilt rows must equal the original data/parity rows exactly
    np.testing.assert_array_equal(np.asarray(rebuilt)[:, 0, :], data[:, 0, :])
    np.testing.assert_array_equal(np.asarray(rebuilt)[:, 1, :], want[:, 3, :])


def test_make_mesh_factoring_pinned(mesh):
    """The sp loop's factoring, pinned per device count (ISSUE 11
    satellite): sp is the largest power of two with sp^2*4 <= n that
    divides n; dp gets the rest. Non-power-of-two counts must factor,
    not crash — a 6-chip pod is a real pod."""
    devs = jax.devices()
    expected = {1: (1, 1), 2: (2, 1), 3: (3, 1), 4: (2, 2),
                5: (5, 1), 6: (3, 2), 7: (7, 1), 8: (4, 2)}
    for n, (dp, sp) in expected.items():
        m = make_mesh(devices=devs[:n])
        assert (m.shape["dp"], m.shape["sp"]) == (dp, sp), \
            f"n={n}: got ({m.shape['dp']}, {m.shape['sp']})"
        assert m.shape["dp"] * m.shape["sp"] == n


def test_sharded_encode_on_non_pow2_mesh(tmp_path):
    """A 6-device (3, 2) mesh — dp 3, sp 2 — must encode exactly like
    the host: mesh factoring edge coverage beyond the 8-device
    fixture."""
    m = make_mesh(devices=jax.devices()[:6])
    rng = np.random.default_rng(21)
    data = rng.integers(0, 256, size=(6, DATA_SHARDS, 512),
                        dtype=np.uint8)
    got = np.asarray(sharded_encode(m, data))
    want = ReedSolomon(backend="numpy").encode(data)
    np.testing.assert_array_equal(got, want)


def test_round_robin_by_size(tmp_path):
    from seaweedfs_tpu.parallel import round_robin_by_size

    sizes = {"a": 50, "b": 40, "c": 30, "d": 20, "e": 10, "f": 0}
    bases = []
    for name, size in sizes.items():
        base = str(tmp_path / name)
        with open(base + ".dat", "wb") as f:
            f.write(b"x" * size)
        bases.append(base)
    # n=1: everything in one bucket, largest first
    one = round_robin_by_size(bases, 1)
    assert len(one) == 1 and len(one[0]) == 6
    assert [os.path.basename(b) for b in one[0][:2]] == ["a", "b"]
    # LPT deal: each volume lands on the then-lightest bucket, so the
    # byte loads balance exactly here: 50+0 / 40+10 / 30+20
    buckets = round_robin_by_size(bases, 3)
    loads = sorted(sum(sizes[os.path.basename(b)] for b in bkt)
                   for bkt in buckets)
    assert loads == [50, 50, 50]
    # empty volumes still cost a slot (not all piled on one bucket)
    empties = []
    for i in range(4):
        base = str(tmp_path / f"z{i}")
        open(base + ".dat", "wb").close()
        empties.append(base)
    spread = round_robin_by_size(empties, 2)
    assert sorted(len(b) for b in spread) == [2, 2]
    # more buckets than volumes: the extras stay empty
    assert [len(b) for b in round_robin_by_size(empties, 8)].count(1) == 4


def test_rotate_shards_permutes_batch(mesh):
    dp = mesh.shape["dp"]
    if dp < 2:
        pytest.skip("needs dp >= 2")
    b = dp
    n = mesh.shape["sp"] * 16
    data = np.arange(b * 14 * n, dtype=np.uint8).reshape(b, 14, n)
    rot = np.asarray(rotate_shards(mesh, jax.numpy.asarray(data), shift=1))
    # blocks move one dp-slot over; with B == dp this is a batch roll
    np.testing.assert_array_equal(rot, np.roll(data, 1, axis=0))
