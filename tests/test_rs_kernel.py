"""JAX RS kernel: bit-exact vs numpy reference, reconstruction properties."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_code import ReedSolomon


def rand_shards(rng, shape):
    return rng.integers(0, 256, shape).astype(np.uint8)


@pytest.fixture(params=["numpy", "jax", "native"])
def rs(request):
    if request.param == "native":
        from seaweedfs_tpu.native import rs_native
        if not rs_native.available():
            pytest.skip("native lib not built")
    return ReedSolomon(backend=request.param)


def test_encode_matches_reference_backend(rs):
    rng = np.random.default_rng(10)
    data = rand_shards(rng, (10, 256))
    parity = rs.encode(data)
    ref = gf256.gf_linear_numpy(rs.matrix[10:], data)
    assert parity.shape == (4, 256)
    assert np.array_equal(parity, ref)


def test_encode_batched(rs):
    rng = np.random.default_rng(11)
    data = rand_shards(rng, (5, 10, 128))
    parity = rs.encode(data)
    assert parity.shape == (5, 4, 128)
    for b in range(5):
        assert np.array_equal(parity[b], rs.encode(data[b]))


def test_verify(rs):
    rng = np.random.default_rng(12)
    data = rand_shards(rng, (10, 64))
    shards = rs.encode_all(data)
    assert rs.verify(shards)
    shards[3, 7] ^= 0xFF
    assert not rs.verify(shards)


@pytest.mark.parametrize("kill", [(0,), (13,), (0, 13), (2, 5, 9, 12), (10, 11, 12, 13)])
def test_reconstruct_any_4_losses(rs, kill):
    rng = np.random.default_rng(13)
    data = rand_shards(rng, (10, 96))
    full = rs.encode_all(data)
    shards = [full[i].copy() if i not in kill else None for i in range(14)]
    rs.reconstruct(shards)
    for i in range(14):
        assert np.array_equal(shards[i], full[i]), f"shard {i} mismatch"


def test_reconstruct_data_only(rs):
    rng = np.random.default_rng(14)
    data = rand_shards(rng, (10, 50))
    full = rs.encode_all(data)
    shards = [full[i].copy() for i in range(14)]
    shards[1] = None
    shards[12] = None
    rs.reconstruct(shards, data_only=True)
    assert np.array_equal(shards[1], full[1])
    assert shards[12] is None  # parity not requested


def test_reconstruct_unrecoverable_raises(rs):
    rng = np.random.default_rng(15)
    data = rand_shards(rng, (10, 8))
    full = rs.encode_all(data)
    shards = [full[i].copy() for i in range(14)]
    for i in (0, 1, 2, 3, 4):
        shards[i] = None
    with pytest.raises(ValueError):
        rs.reconstruct(shards)


def test_reconstruct_from_parity_heavy_subset(rs):
    # use all 4 parity shards + 6 data shards
    rng = np.random.default_rng(16)
    data = rand_shards(rng, (10, 40))
    full = rs.encode_all(data)
    present = [0, 1, 2, 3, 4, 5, 10, 11, 12, 13]
    out = rs.reconstruct_some(present, [6, 7, 8, 9], full[present])
    assert np.array_equal(out, full[6:10])


def test_kernel_bits_roundtrip():
    import jax.numpy as jnp
    from seaweedfs_tpu.ops import rs_kernel
    rng = np.random.default_rng(17)
    x = rand_shards(rng, (3, 10, 128))
    bits = rs_kernel.bits_expand(jnp.asarray(x))
    assert bits.shape == (3, 80, 128)
    back = rs_kernel.bits_pack(bits)
    assert np.array_equal(np.asarray(back), x)


def test_jax_vs_numpy_large_random_matrices():
    rng = np.random.default_rng(18)
    rs_j = ReedSolomon(backend="jax")
    for _ in range(3):
        m = rng.integers(0, 256, (6, 12)).astype(np.uint8)
        data = rand_shards(rng, (12, 200))
        from seaweedfs_tpu.ops import rs_kernel
        out = rs_kernel.apply_matrix(m, data)
        assert np.array_equal(out, gf256.gf_linear_numpy(m, data))


def test_pallas_backend_byte_equality(monkeypatch):
    """The opt-in Pallas codec matches numpy byte-for-byte on encode
    and reconstruct, including odd lane counts that exercise the
    128-lane padding. Off the chip the program itself never interprets
    (test_pallas_backend_raises_off_chip), so THIS test asks for the
    Pallas interpreter: a test-side wrapper around gf_linear_pallas."""
    import functools

    import numpy as np

    from seaweedfs_tpu.ops import rs_pallas
    from seaweedfs_tpu.ops.rs_code import ReedSolomon

    monkeypatch.setattr(
        rs_pallas, "gf_linear_pallas",
        functools.partial(rs_pallas.gf_linear_pallas, interpret=True))
    rng = np.random.default_rng(5)
    ref = ReedSolomon(backend="numpy")
    pal = ReedSolomon(backend="pallas")
    lane_cases = (128, 1000, 4096 + 17,
                  rs_pallas.TILE + 257)   # crosses a tile boundary
    for lanes in lane_cases:
        data = rng.integers(0, 256, size=(10, lanes), dtype=np.uint8)
        np.testing.assert_array_equal(pal.encode(data), ref.encode(data))
    # empty batch round-trips without dispatch
    empty = np.zeros((0, 10, 256), dtype=np.uint8)
    assert pal.encode(empty).shape == (0, 4, 256)
    data = rng.integers(0, 256, size=(10, 777), dtype=np.uint8)
    full = ref.encode_all(data)
    present = [0, 2, 3, 4, 6, 7, 8, 9, 10, 12]
    src = full[present, :]
    np.testing.assert_array_equal(
        pal.reconstruct_some(present, [1, 5, 11, 13], src),
        ref.reconstruct_some(present, [1, 5, 11, 13], src))


def test_pallas_backend_raises_off_chip():
    """No quiet interpret mode: off the TPU the Pallas codec's entry
    compiles for the chip or raises — it never falls back to the
    interpreter (or to another backend) by itself."""
    import jax

    assert jax.default_backend() != "tpu"
    data = np.zeros((10, 256), dtype=np.uint8)
    with pytest.raises(Exception) as ei:
        ReedSolomon(backend="pallas").encode(data)
    assert "interpret" in str(ei.value).lower()
    # asked for by the caller, the interpreter still works
    from seaweedfs_tpu.ops import rs_pallas
    out = rs_pallas.gf_linear_pallas(
        ReedSolomon().matrix[10:], data, interpret=True)
    assert np.asarray(out).shape == (4, 256)


def test_named_backend_is_never_substituted(monkeypatch):
    """backend="native" with no library raises (NativeUnavailable);
    only "auto" may settle for numpy."""
    from seaweedfs_tpu.native import rs_native

    def broken():
        raise rs_native.NativeUnavailable("no g++ on this machine")

    monkeypatch.setattr(rs_native, "_load", broken)
    data = np.arange(10 * 64, dtype=np.uint8).reshape(10, 64)
    with pytest.raises(rs_native.NativeUnavailable):
        ReedSolomon(backend="native").encode(data)
    assert not rs_native.available()
    want = gf256.gf_linear_numpy(ReedSolomon().matrix[10:], data)
    assert np.array_equal(ReedSolomon(backend="auto").encode(data), want)
