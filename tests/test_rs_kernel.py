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


# --- a lent result buffer (ISSUE 30) -------------------------------------------
# apply_matrix_async(..., out=) copies each fetched slab into memory the
# caller keeps, where it would fill a fresh array a dispatch.

def _result_counts():
    from seaweedfs_tpu.stats.metrics import RsResultBuffersCounter
    return {s: RsResultBuffersCounter.labels(s).value
            for s in ("lent", "fresh")}


@pytest.mark.parametrize("rows", [4, 2])
@pytest.mark.parametrize("n", [
    1,                  # one slab, nearly all padding
    70_000,             # one slab with a padded tail
    2 * 65_536 + 777,   # several slabs, the last one padded
    3 * 65_536,         # several slabs, none padded
])
def test_result_lands_in_the_lent_buffer(monkeypatch, rows, n):
    """result() with out= returns `out` itself, byte-equal to the call
    without it, and touches nothing of the lender's memory beside it."""
    from seaweedfs_tpu.ops import rs_kernel

    monkeypatch.setattr(rs_kernel, "_MAX_SLAB", rs_kernel._MIN_SLAB)
    rng = np.random.default_rng(70 + rows)
    matrix = rand_shards(rng, (rows, 10))
    # as ec/fleet.py lends it: the last rows and first lanes of a stripe
    stripe = np.full((14, n + 5), 0xA5, dtype=np.uint8)
    stripe[:10, :n] = rand_shards(rng, (10, n))
    data, out = stripe[:10, :n], stripe[10:10 + rows, :n]
    want = rs_kernel.apply_matrix_async(matrix, data.copy()).result()
    assert np.array_equal(want, gf256.gf_linear_numpy(matrix, data))
    before = _result_counts()
    got = rs_kernel.apply_matrix_async(matrix, data, out=out).result()
    assert got is out
    assert np.array_equal(got, want)
    assert (stripe[10 + rows:] == 0xA5).all() and (stripe[:, n:] == 0xA5).all()
    after = _result_counts()
    assert (after["lent"] - before["lent"], after["fresh"] - before["fresh"]) \
        == (1, 0)


def test_result_without_out_counts_fresh():
    from seaweedfs_tpu.ops import rs_kernel

    rng = np.random.default_rng(72)
    matrix, data = rand_shards(rng, (4, 10)), rand_shards(rng, (3, 10, 500))
    before = _result_counts()
    got = rs_kernel.apply_matrix_async(matrix, data).result()
    assert np.array_equal(got, gf256.gf_linear_numpy(matrix, data))
    # nothing to fetch, nothing counted; and an empty `out` is handed back
    empty = np.empty((4, 0), dtype=np.uint8)
    assert rs_kernel.apply_matrix_async(
        matrix, data[0][:, :0], out=empty).result() is empty
    after = _result_counts()
    assert (after["lent"] - before["lent"], after["fresh"] - before["fresh"]) \
        == (0, 1)


@pytest.mark.parametrize("case, shape, out, message", [
    ("rows", (10, 300), np.empty((3, 300), np.uint8), "has shape"),
    ("lanes", (10, 300), np.empty((4, 301), np.uint8), "has shape"),
    ("flat", (10, 300), np.empty(1200, np.uint8), "has shape"),
    ("dtype", (10, 300), np.empty((4, 300), np.int8), "uint8"),
    ("not_an_array", (10, 300), bytearray(1200), "uint8"),
    ("strided_rows", (10, 300), np.empty((4, 600), np.uint8)[:, ::2],
     "contiguous"),
    ("stacked_input", (2, 10, 150), np.empty((4, 300), np.uint8), "2-D"),
    ("stacked_both", (2, 10, 150), np.empty((2, 4, 150), np.uint8), "2-D"),
])
def test_out_that_cannot_take_the_result_raises_at_the_call(monkeypatch, case,
                                                            shape, out,
                                                            message):
    """At the call, on the caller's thread and before any placement —
    not from result(), which a scheduler runs on its retire thread."""
    from seaweedfs_tpu.ops import rs_kernel

    def no_dispatch(*a, **kw):
        raise AssertionError("dispatched before the check")

    monkeypatch.setattr(rs_kernel, "_submit_slabs", no_dispatch)
    rng = np.random.default_rng(73)
    matrix, data = rand_shards(rng, (4, 10)), rand_shards(rng, shape)
    with pytest.raises(ValueError, match=message):
        rs_kernel.apply_matrix_async(matrix, data, out=out)


def test_read_only_out_raises_at_the_call():
    from seaweedfs_tpu.ops import rs_kernel

    rng = np.random.default_rng(74)
    out = np.empty((4, 300), np.uint8)
    out.flags.writeable = False
    with pytest.raises(ValueError, match="writable"):
        rs_kernel.apply_matrix_async(rand_shards(rng, (4, 10)),
                                     rand_shards(rng, (10, 300)), out=out)


@pytest.mark.parametrize("op", ["encode", "reconstruct"])
def test_codec_passes_out_through_on_jax_and_host_backends_keep_their_own(
        rs, op):
    """encode_async / reconstruct_some_async: the jax backend's result IS
    the lent array; a host codec allocates inside and ignores it."""
    rng = np.random.default_rng(75)
    data = rand_shards(rng, (10, 1000))
    full = np.concatenate([data, ReedSolomon(backend="numpy").encode(data)])
    if op == "encode":
        out = np.zeros((4, 1000), np.uint8)
        got = rs.encode_async(data, out=out).result()
        want = full[10:]
    else:
        present = [1, 2, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
        out = np.zeros((2, 1000), np.uint8)
        got = rs.reconstruct_some_async(present, [0, 3], full[present],
                                        out=out).result()
        want = full[[0, 3]]
    assert np.array_equal(got, want)
    assert (got is out) == (rs.backend == "jax")


# --- compare-and-count: a verify's counts, not its parity --------------------

def _plain_counts(stripe, block):
    """The reference: numpy re-encode, numpy compare, per parity row and
    block of lanes the differing bytes and the first differing lane."""
    parity = ReedSolomon(backend="numpy").encode(stripe[:10])
    differ = (parity != stripe[10:]).reshape(4, -1, block)
    lane = np.arange(block)
    return differ.sum(axis=2), \
        np.where(differ, lane, block).min(axis=2)


@pytest.mark.parametrize("flips", [
    "none", "data-row", "one-parity-row", "first-lane", "last-lane",
    "block-boundary"])
@pytest.mark.parametrize("blocks", [
    16,            # one full slab of 2^16 lanes
    5,             # one slab, a padded tail
    2 * 16 + 3,    # several slabs, the last one padded
])
def test_verify_counts_equal_a_numpy_reencode_and_compare(monkeypatch, blocks,
                                                          flips):
    from seaweedfs_tpu.ops import rs_kernel

    block = rs_kernel.VERIFY_BLOCK
    assert rs_kernel._MIN_SLAB == 16 * block
    monkeypatch.setattr(rs_kernel, "_MAX_SLAB", rs_kernel._MIN_SLAB)
    n = blocks * block
    rng = np.random.default_rng(90 + blocks)
    rs = ReedSolomon(backend="numpy")
    stripe = np.empty((14, n), dtype=np.uint8)
    stripe[:10] = rand_shards(rng, (10, n))
    stripe[10:] = rs.encode(stripe[:10])
    for row, lane in {
            "none": [],
            "data-row": [(4, 3 * block + 17), (4, 3 * block + 18)],
            "one-parity-row": [(12, block + 5), (12, n - 7), (12, 9)],
            "first-lane": [(10, 0), (7, 0)],
            "last-lane": [(13, n - 1), (0, n - 1)],
            # the last lane of one block and the first of the next
            "block-boundary": [(11, 2 * block - 1), (11, 2 * block),
                               (2, 2 * block - 1)],
    }[flips]:
        stripe[row, lane] ^= 0x5A
    want_counts, want_firsts = _plain_counts(stripe, block)
    counts, firsts = rs_kernel.verify_stripe_async(
        rs.matrix[10:], stripe).result()
    assert counts.shape == firsts.shape == (4, blocks)
    assert np.array_equal(counts, want_counts)
    assert np.array_equal(firsts, want_firsts)
    if flips == "none":
        assert not counts.any() and (firsts == block).all()
    if flips == "data-row":            # every parity row, the same lanes
        assert (counts[:, 3] == 2).all() and (firsts[:, 3] == 17).all()
        assert counts.sum() == 8


def test_verify_stripe_takes_a_view_and_fetches_counts_only(monkeypatch):
    """As ec/fleet.py hands it over: the first lanes of a wider buffer.
    What comes back is KB; no result memory is lent or made."""
    from seaweedfs_tpu.ops import rs_kernel

    block = rs_kernel.VERIFY_BLOCK
    rng = np.random.default_rng(97)
    rs = ReedSolomon(backend="numpy")
    buf = np.full((14, 3 * block + 100), 0xA5, dtype=np.uint8)
    stripe = buf[:, :3 * block]
    stripe[:10] = rand_shards(rng, (10, 3 * block))
    stripe[10:] = rs.encode(stripe[:10])
    kept = buf.copy()
    before = _result_counts()
    fetched = []
    real = rs_kernel._phase

    def phase(name, **tags):
        if name == "fetch":
            fetched.append(tags["bytes"])
        return real(name, **tags)

    monkeypatch.setattr(rs_kernel, "_phase", phase)
    counts, firsts = rs_kernel.verify_stripe_async(
        rs.matrix[10:], stripe).result()
    assert not counts.any() and counts.shape == (4, 3)
    assert np.array_equal(buf, kept)
    assert _result_counts() == before
    assert fetched == [2 * 4 * 16 * 4]       # one slab: int32 [2, 4, 16]


@pytest.mark.parametrize("case, stripe, message", [
    ("not whole blocks", np.zeros((14, 4097), dtype=np.uint8), "blocks"),
    ("ten rows", np.zeros((10, 4096), dtype=np.uint8), "stripe"),
    ("stacked", np.zeros((2, 14, 4096), dtype=np.uint8), "stripe"),
    ("dtype", np.zeros((14, 4096), dtype=np.int32), "stripe"),
])
def test_a_stripe_that_cannot_be_verified_raises_at_the_call(case, stripe,
                                                             message):
    from seaweedfs_tpu.ops import rs_kernel

    with pytest.raises(ValueError, match=message):
        rs_kernel.verify_stripe_async(ReedSolomon().matrix[10:], stripe)


# --- a tail slab's padding (ISSUE 32) ----------------------------------------
# A dispatch is placed as whole power-of-two slabs. A caller that brings the
# room (a staging buffer's slack, `lanes=`) has its tail slab sliced like
# every other; one that does not has it copied into a fresh zeroed array.

def _tail_counts():
    from seaweedfs_tpu.stats.metrics import RsTailSlabsCounter
    return {p: RsTailSlabsCounter.labels(p).value
            for p in ("in_place", "copied")}


@pytest.fixture
def placed(monkeypatch):
    """The slab loop as a one-chip host runs it — no lane sharding, so
    a slab goes to jnp.asarray as the view it is — with every slab it
    places recorded (uint8; a matrix's bits are int8)."""
    from seaweedfs_tpu.ops import rs_kernel

    seen = []
    real = rs_kernel.jnp

    class Recording:
        def __getattr__(self, name):
            return getattr(real, name)

        def asarray(self, x):
            if x.dtype == np.uint8:
                seen.append(x)
            return real.asarray(x)

    monkeypatch.setattr(rs_kernel, "_lane_sharding", lambda: None)
    monkeypatch.setattr(rs_kernel, "jnp", Recording())
    return seen


def test_the_bucket_rule_has_one_statement(monkeypatch):
    """placed_lanes is the sum of the widths the slab loop places."""
    from seaweedfs_tpu.ops import rs_kernel

    lo, hi = rs_kernel._MIN_SLAB, rs_kernel._MAX_SLAB
    assert (lo, hi) == (1 << 16, 1 << 22)
    for lanes, want in [
            (0, 0), (1, lo), (lo, lo), (lo + 1, 2 * lo), (hi - 1, hi),
            (hi, hi), (hi + 1, hi + lo), (3 * hi, 3 * hi),
            # the cells' dispatches: a one-volume rebuild and re-verify,
            # the pool's verify, a two-shard rebuild, an encode
            (12_000_370, 3 * hi), (12_001_280, 3 * hi),
            (13_107_200, 13_107_200), (12_705_742, 3 * hi + (1 << 17)),
            (12 << 20, 3 * hi)]:
        assert rs_kernel.placed_lanes(lanes) == want
        assert [w for w, _ in rs_kernel._slabs(lanes)] == \
            [hi] * (lanes // hi) + ([lanes % hi] if lanes % hi else [])
    monkeypatch.setattr(rs_kernel, "_MAX_SLAB", 4 * lo)
    assert list(rs_kernel._slabs(9 * lo + 5)) == \
        [(4 * lo, 4 * lo), (4 * lo, 4 * lo), (lo + 5, 2 * lo)]


# (lanes of the dispatch in blocks, lanes the input array has beyond
# them as a share of the tail slab's slack) -> what is counted
TAILS = {
    # 2 slabs of 32 blocks and one of 16: nothing to pad
    "whole_slabs": (2 * 32 + 16, 0, (0, 0)),
    # a tail of 17 blocks in a slab of 32, the buffer reaching its end
    "tail_with_its_slack": (32 + 17, 1.0, (1, 0)),
    # the same dispatch as a bare [S, n]
    "bare_tail": (32 + 17, 0, (0, 1)),
    # room that stops short of the slab's end is no room
    "slack_too_short": (32 + 17, 0.5, (0, 1)),
    # one slab, nearly all of it slack
    "one_block": (1, 1.0, (1, 0)),
}


@pytest.mark.parametrize("program", ["map4", "map1", "verify"])
@pytest.mark.parametrize("case", sorted(TAILS))
def test_tail_slab_is_sliced_where_the_input_has_the_room(monkeypatch, placed,
                                                          case, program):
    """Whole slabs and a tail that brings its slack are slices of the
    caller's array, every one; a bare tail is the one copy. The result
    is the same in all of them, and whatever lies in the slack — here
    a pattern in every row — reaches none of it."""
    from seaweedfs_tpu.ops import rs_kernel

    block = rs_kernel.VERIFY_BLOCK
    monkeypatch.setattr(rs_kernel, "_MAX_SLAB", 2 * rs_kernel._MIN_SLAB)
    blocks, share, counted = TAILS[case]
    n = blocks * block
    slack = rs_kernel.placed_lanes(n) - n
    rng = np.random.default_rng(110 + blocks)
    rs = ReedSolomon(backend="numpy")
    buf = np.full((14, n + int(share * slack)), 0xA5, dtype=np.uint8)
    buf[:10, :n] = rand_shards(rng, (10, n))
    buf[10:, :n] = rs.encode(buf[:10, :n])
    for row, lane in [(3, n - 1), (12, n - block), (12, 5)]:
        buf[row, lane] ^= 0x5A
    kept = buf.copy()
    before = _tail_counts()
    if program == "verify":
        got = rs_kernel.verify_stripe_async(rs.matrix[10:], buf,
                                            lanes=n).result()
        want = _plain_counts(kept[:, :n], block)
        assert got[0].shape == got[1].shape == (4, blocks)
        rows = 14
    else:
        matrix = rs.matrix[10:] if program == "map4" else \
            rs.decode_matrix([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [0])
        out = np.full((4, n), 0xEE, dtype=np.uint8)[:len(matrix)]
        got = rs_kernel.apply_matrix_async(matrix, buf[:10], out=out,
                                           lanes=n).result()
        assert got is out
        got, want = [got], [gf256.gf_linear_numpy(matrix, kept[:10, :n])]
        rows = 10
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert np.array_equal(buf, kept), "the dispatch layer wrote to its input"
    after = _tail_counts()
    assert (after["in_place"] - before["in_place"],
            after["copied"] - before["copied"]) == counted
    widths = [slab for _, slab in rs_kernel._slabs(n)]
    assert [x.shape for x in placed] == [(rows, w) for w in widths]
    assert [np.shares_memory(x, buf) for x in placed] == \
        [True] * (len(widths) - counted[1]) + [False] * counted[1]


def test_default_lanes_are_the_whole_input_and_a_short_tail_is_copied(placed):
    """Without `lanes` nothing changes for a caller: the input is the
    dispatch, stacked inputs too, and a short tail counts `copied`."""
    from seaweedfs_tpu.ops import rs_kernel

    rng = np.random.default_rng(120)
    matrix = rand_shards(rng, (4, 10))
    before = _tail_counts()
    for data in (rand_shards(rng, (10, 700)), rand_shards(rng, (3, 10, 500))):
        got = rs_kernel.apply_matrix_async(matrix, data).result()
        assert np.array_equal(got, gf256.gf_linear_numpy(matrix, data))
    after = _tail_counts()
    assert (after["in_place"] - before["in_place"],
            after["copied"] - before["copied"]) == (0, 2)


@pytest.mark.parametrize("case, shape, lanes, message", [
    ("more_than_there_is", (10, 300), 301, "lanes=301"),
    ("negative", (10, 300), -1, "lanes=-1"),
    ("stacked", (2, 10, 150), 100, "2-D"),
])
def test_lanes_that_the_input_cannot_have_raise_at_the_call(monkeypatch, case,
                                                            shape, lanes,
                                                            message):
    from seaweedfs_tpu.ops import rs_kernel

    monkeypatch.setattr(
        rs_kernel, "_submit_slabs",
        lambda *a, **kw: pytest.fail("dispatched before the check"))
    rng = np.random.default_rng(121)
    with pytest.raises(ValueError, match=message):
        rs_kernel.apply_matrix_async(rand_shards(rng, (4, 10)),
                                     rand_shards(rng, shape), lanes=lanes)
    if len(shape) == 2:
        beyond = 8192 if lanes > 0 else -4096
        with pytest.raises(ValueError, match=f"lanes={beyond}"):
            rs_kernel.verify_stripe_async(
                ReedSolomon().matrix[10:],
                np.zeros((14, 4096), dtype=np.uint8), lanes=beyond)


@pytest.mark.parametrize("op", ["encode", "reconstruct"])
def test_codec_passes_lanes_through_and_host_backends_map_that_many(rs, op):
    """encode_async / reconstruct_some_async with lanes=: every backend
    maps the first `lanes` of a wider input and nothing after them."""
    rng = np.random.default_rng(122)
    data = np.full((10, 1300), 0xA5, dtype=np.uint8)
    data[:, :1000] = rand_shards(rng, (10, 1000))
    full = np.concatenate(
        [data, ReedSolomon(backend="numpy").encode(data)])[:, :1000]
    if op == "encode":
        got = rs.encode_async(data, lanes=1000).result()
        want = full[10:]
    else:
        present = [1, 2, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
        wide = np.concatenate([data, np.zeros((4, 1300), np.uint8)])
        wide[:, :1000] = full
        got = rs.reconstruct_some_async(present, [0, 3], wide[present],
                                        lanes=1000).result()
        want = full[[0, 3]]
    assert got.shape == want.shape and np.array_equal(got, want)
