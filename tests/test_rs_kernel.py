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


def test_pallas_is_not_a_backend(capsys):
    """The fused Pallas codec went in PR 29 (never run in a cell): its
    name is an unknown backend to the codec and to the volume server's
    `-ec.encoder`."""
    from seaweedfs_tpu.command import servers

    with pytest.raises(ValueError, match="unknown RS backend"):
        ReedSolomon(backend="pallas")
    parser = servers._volume_parser()
    with pytest.raises(SystemExit):
        parser.parse_args(["-ec.encoder", "pallas"])
    assert "invalid choice: 'pallas'" in capsys.readouterr().err
    for name in ("auto", "jax", "native", "numpy"):
        assert parser.parse_args(["-ec.encoder", name]).ec_encoder == name


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
