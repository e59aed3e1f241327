"""Cross-volume fleet EC scheduler tests (ec/fleet.py).

The fleet contract is byte-identity: fusing many volumes' chunks into
shared RS dispatches, feeding them from a reader pool, and retiring
writes through per-volume writer lanes must produce exactly the shard
files the serial per-volume encoder writes. Small geometry (the
test_ec.py pattern) keeps volumes a few KB while still exercising
multi-row packing, tail padding, the oversized-volume fallback, and
pipeline depth > 1.
"""

import filecmp
import functools
import os

import numpy as np
import pytest

from seaweedfs_tpu import ec
from seaweedfs_tpu.ec import fleet, store_ec
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.ops.rs_code import ReedSolomon, DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.stats.metrics import FleetRebuildGroupsCounter
from tests.test_scrub import _flip_byte

LARGE = 2048
SMALL = 256
ROW = DATA_SHARDS * SMALL  # 2560 bytes per small row

# volume sizes chosen to hit: empty, sub-row, exact row, multi-row with
# ragged tail, and (30KB > 10*LARGE) the per-volume large-row fallback
SIZES = [0, 1, 700, ROW, 3 * ROW + 123, 30 << 10]


def _make_volumes(root, sizes, seed=0):
    rng = np.random.default_rng(seed)
    bases = []
    for i, sz in enumerate(sizes):
        base = os.path.join(root, f"{i}")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, sz, dtype=np.uint8).tobytes())
        bases.append(base)
    return bases


def _serial_twin(bases, tag="serial"):
    """Hard-link each .dat under a sibling name for the serial run."""
    twins = []
    for base in bases:
        twin = f"{base}.{tag}"
        os.link(base + ".dat", twin + ".dat")
        twins.append(twin)
    return twins


def _assert_shards_equal(got_bases, want_bases):
    for g, w in zip(got_bases, want_bases):
        for sid in range(TOTAL_SHARDS):
            gp, wp = shard_file_name(g, sid), shard_file_name(w, sid)
            assert os.path.exists(gp), f"missing {gp}"
            assert filecmp.cmp(gp, wp, shallow=False), \
                f"shard {sid} of {os.path.basename(g)} differs"


def test_fleet_encode_byte_identical_to_serial(tmp_path):
    bases = _make_volumes(str(tmp_path), SIZES)
    twins = _serial_twin(bases)
    for t in twins:
        ec.write_ec_files(t, backend="numpy", large_block=LARGE,
                          small_block=SMALL, chunk=512)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=LARGE,
                               small_block=SMALL, chunk=512)
    _assert_shards_equal(bases, twins)


def test_fleet_encode_single_volume_degenerates(tmp_path):
    """One volume through the fleet == the serial path (the scheduler
    must not require a crowd)."""
    bases = _make_volumes(str(tmp_path), [3 * ROW + 5])
    twins = _serial_twin(bases)
    ec.write_ec_files(twins[0], backend="numpy", large_block=LARGE,
                      small_block=SMALL, chunk=512)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=LARGE,
                               small_block=SMALL, chunk=512)
    _assert_shards_equal(bases, twins)


def test_fleet_encode_parity_rows_verify(tmp_path):
    """Pipeline-ordering regression guard (fleet side): with depth >= 2
    and several dispatches in flight, every row's parity must verify
    against that SAME row's data — an out-of-order parity retire
    corrupts shards silently and only row-wise verify catches it."""
    sizes = [5 * ROW + 7, 2 * ROW, 7 * ROW + 1111]
    bases = _make_volumes(str(tmp_path), sizes, seed=5)
    # chunk=512 < one row, so every row is its own dispatch: many
    # in-flight handles per volume
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=LARGE,
                               small_block=SMALL, chunk=512, depth=3)
    rs = ReedSolomon(backend="numpy")
    for base in bases:
        shard_bytes = [open(shard_file_name(base, i), "rb").read()
                       for i in range(TOTAL_SHARDS)]
        n_rows = len(shard_bytes[0]) // SMALL
        assert n_rows > 1
        for r in range(n_rows):
            row = np.stack([np.frombuffer(
                s[r * SMALL:(r + 1) * SMALL], dtype=np.uint8)
                for s in shard_bytes])
            assert rs.verify(row), f"row {r} of {base} fails verify"


def test_serial_pipeline_ordering_depth2(tmp_path):
    """Same guard for the per-volume pipeline (encoder._EncodePipeline,
    default depth 2): chunk-per-row dispatch, row-wise verify."""
    bases = _make_volumes(str(tmp_path), [6 * ROW + 99], seed=6)
    ec.write_ec_files(bases[0], backend="numpy", large_block=LARGE,
                      small_block=SMALL, chunk=512)
    rs = ReedSolomon(backend="numpy")
    shard_bytes = [open(shard_file_name(bases[0], i), "rb").read()
                   for i in range(TOTAL_SHARDS)]
    n_rows = len(shard_bytes[0]) // SMALL
    assert n_rows >= 6  # enough dispatches to keep depth-2 busy
    for r in range(n_rows):
        row = np.stack([np.frombuffer(
            s[r * SMALL:(r + 1) * SMALL], dtype=np.uint8)
            for s in shard_bytes])
        assert rs.verify(row), f"row {r} fails verify"


def test_fleet_rebuild_byte_identical(tmp_path):
    """Different volumes missing different shard sets: volumes sharing
    a (present, missing) signature fuse into one dispatch group, the
    rest split — all must come back byte-identical."""
    sizes = [2 * ROW + 17, 2 * ROW + 17, ROW, 4 * ROW]
    bases = _make_volumes(str(tmp_path), sizes, seed=2)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=LARGE,
                               small_block=SMALL, chunk=512)
    originals = {(b, sid): open(shard_file_name(b, sid), "rb").read()
                 for b in bases for sid in range(TOTAL_SHARDS)}
    drops = ([0, 13], [0, 13], [3], [1, 2, 11, 12])  # two share a group
    for base, drop in zip(bases, drops):
        for sid in drop:
            os.remove(shard_file_name(base, sid))
    rebuilt = fleet.fleet_rebuild_ec_files(bases, backend="numpy",
                                           chunk=512)
    for base, drop in zip(bases, drops):
        assert rebuilt[base] == list(drop)
        for sid in range(TOTAL_SHARDS):
            with open(shard_file_name(base, sid), "rb") as f:
                assert f.read() == originals[(base, sid)], \
                    f"shard {sid} of {base}"


GIB_SHARD = -(-(1 << 30) // DATA_SHARDS)


@pytest.mark.parametrize("chunk, sizes, span, per_batch", [
    # the cell node-repair.rebuild: both volumes in every dispatch
    (128 << 20, [GIB_SHARD] * 2, 6316129, 2),
    # the one-volume_id request
    (128 << 20, [GIB_SHARD], 11930465, 1),
    # the deployment's published scale: spans stop at a small block and
    # a dispatch stacks as many as fill a chunk, not one a volume
    (128 << 20, [GIB_SHARD] * 128, 1042468, 12),
    # small volumes: whole shards, all stacked
    (128 << 20, [40_000] * 32, 40_000, 32),
    # one big volume among small ones
    (128 << 20, [GIB_SHARD] + [40_000] * 127, 1042468, 12),
    # host backends' chunk
    (16 << 20, [GIB_SHARD] * 2, 1042468, 1),
    # a chunk below a small block a row (tests): one span a dispatch
    (512, [700] * 3, 50, 1),
    (1, [0], 1, 1),
])
def test_rebuild_span_rule(chunk, sizes, span, per_batch):
    """`chunk` is the input bytes of ALL ten rows of one stacked
    dispatch, as in encode."""
    assert fleet._stacked_spans(chunk, sizes) == (span, per_batch)
    assert DATA_SHARDS * span * per_batch <= max(chunk, DATA_SHARDS)
    assert span <= max(1, max(sizes)) and per_batch <= len(sizes)


def test_rebuild_of_many_volumes_in_one_group_keeps_its_spans_wide(
        tmp_path, monkeypatch):
    """48 volumes that lost the same shards are ONE group. Their spans
    stop narrowing at a small block (here 512 bytes), so the pass makes
    a bounded number of reads (ten opens each) and dispatches: without
    the floor it would cut every shard into spans of 51 bytes, 24 times
    the reads."""
    small_block = 512
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", small_block)
    n, chunk = 48, DATA_SHARDS * 4 * small_block
    bases = _make_volumes(str(tmp_path), [5 * ROW] * n, seed=9)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=LARGE,
                               small_block=SMALL)
    shard_size = os.path.getsize(shard_file_name(bases[0], 0))
    assert shard_size == 5 * SMALL
    originals = {(b, sid): open(shard_file_name(b, sid), "rb").read()
                 for b in bases for sid in (0, 3)}
    for base in bases:
        for sid in (0, 3):
            os.remove(shard_file_name(base, sid))
    reads, batches = [], []
    read, reconstruct = fleet._read_present_span_into, \
        fleet._Dispatcher.reconstruct_lanes

    def counted_read(*args):
        reads.append(args[4])
        return read(*args)

    def counted_reconstruct(self, present, missing, buf, cuts, done):
        batches.append(len(cuts))
        return reconstruct(self, present, missing, buf, cuts, done)

    monkeypatch.setattr(fleet, "_read_present_span_into", counted_read)
    monkeypatch.setattr(fleet._Dispatcher, "reconstruct_lanes",
                        counted_reconstruct)
    groups = FleetRebuildGroupsCounter.labels().value
    rebuilt = fleet.fleet_rebuild_ec_files(bases, backend="numpy",
                                           chunk=chunk)
    assert FleetRebuildGroupsCounter.labels().value - groups == 1
    assert rebuilt == {b: [0, 3] for b in bases}
    for (base, sid), want in originals.items():
        with open(shard_file_name(base, sid), "rb") as f:
            assert f.read() == want, f"shard {sid} of {base}"
    span, per_batch = fleet._stacked_spans(chunk, [shard_size] * n)
    assert (span, per_batch) == (427, 4)
    assert set(reads) == {span}
    assert len(reads) == n * 3                  # three spans a shard
    assert batches == [per_batch] * (n * 3 // per_batch)


def test_rebuild_wanted_partial(tmp_path):
    """Satellite: rebuild_ec_files(wanted=...) regenerates ONLY the
    wanted subset — the decode-to-volume path depends on not paying for
    parity it will never read. Covers the serial and fleet rebuilds."""
    bases = _make_volumes(str(tmp_path), [3 * ROW + 200, 3 * ROW + 200],
                          seed=3)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=LARGE,
                               small_block=SMALL, chunk=512)
    originals = {(b, sid): open(shard_file_name(b, sid), "rb").read()
                 for b in bases for sid in range(TOTAL_SHARDS)}
    for base in bases:
        for sid in (0, 7, 11, 13):
            os.remove(shard_file_name(base, sid))
    # serial: only data shards wanted -> parity stays missing
    got = ec.rebuild_ec_files(bases[0], backend="numpy", chunk=512,
                              wanted=list(range(DATA_SHARDS)))
    assert sorted(got) == [0, 7]
    for sid in (0, 7):
        with open(shard_file_name(bases[0], sid), "rb") as f:
            assert f.read() == originals[(bases[0], sid)]
    for sid in (11, 13):
        assert not os.path.exists(shard_file_name(bases[0], sid))
    # fleet: same wanted contract
    rebuilt = fleet.fleet_rebuild_ec_files(
        [bases[1]], backend="numpy", chunk=512,
        wanted=list(range(DATA_SHARDS)))
    assert rebuilt[bases[1]] == [0, 7]
    for sid in (0, 7):
        with open(shard_file_name(bases[1], sid), "rb") as f:
            assert f.read() == originals[(bases[1], sid)]
    for sid in (11, 13):
        assert not os.path.exists(shard_file_name(bases[1], sid))


def test_fleet_rebuild_too_few_shards_raises(tmp_path):
    bases = _make_volumes(str(tmp_path), [2 * ROW], seed=4)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=LARGE,
                               small_block=SMALL, chunk=512)
    for sid in range(5):
        os.remove(shard_file_name(bases[0], sid))
    with pytest.raises(ValueError):
        fleet.fleet_rebuild_ec_files(bases, backend="numpy", chunk=512)


def test_round_robin_by_size_balances(tmp_path):
    sizes = [10 * ROW, ROW, 2 * ROW, 7 * ROW, 7 * ROW, 0, 3 * ROW]
    bases = _make_volumes(str(tmp_path), sizes, seed=7)
    from seaweedfs_tpu.parallel import round_robin_by_size
    buckets = round_robin_by_size(bases, 3)
    assert sorted(b for g in buckets for b in g) == sorted(bases)
    loads = [sum(os.path.getsize(b + ".dat") for b in g) for g in buckets]
    # LPT deal: no shard's byte-load exceeds another's by more than the
    # largest volume
    assert max(loads) - min(loads) <= max(sizes)
    # empty volumes still get dealt somewhere
    assert sum(len(g) for g in buckets) == len(bases)


def test_fleet_sharded_over_host_shards(tmp_path):
    """fleet_write_ec_files_sharded on a host backend: volumes dealt to
    parallel per-shard schedulers, output byte-identical to serial."""
    sizes = [3 * ROW + 1, ROW, 5 * ROW, 2 * ROW + 77]
    bases = _make_volumes(str(tmp_path), sizes, seed=8)
    twins = _serial_twin(bases)
    for t in twins:
        ec.write_ec_files(t, backend="numpy", large_block=LARGE,
                          small_block=SMALL, chunk=512)
    from seaweedfs_tpu.parallel import fleet_write_ec_files_sharded
    fleet_write_ec_files_sharded(bases, devices=[None, None],
                                 backend="numpy", large_block=LARGE,
                                 small_block=SMALL, chunk=512)
    _assert_shards_equal(bases, twins)


def test_generate_ec_shards_batch_matches_serial(tmp_path):
    """store_ec.generate_ec_shards_batch: many volumes in one fused
    pass == generate_ec_shards per volume, including the .ecx index."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    store = Store([str(tmp_path)])
    rng = np.random.default_rng(9)
    for vid in (1, 2, 3):
        store.add_volume(vid)
        v = store.find_volume(vid)
        for i in range(1, 6):
            v.write_needle(Needle(
                id=i, cookie=0x20 + i,
                data=rng.integers(0, 256, int(rng.integers(100, 4000)),
                                  dtype=np.uint8).tobytes()))
    # expected output: the serial per-volume generate, run on hard-
    # linked copies of the frozen volume files
    expected = {}
    for vid in (1, 2, 3):
        v = store.find_volume(vid)
        v.sync()
        base = v.file_name()
        twin = os.path.join(str(tmp_path), f"twin{vid}")
        os.link(base + ".dat", twin + ".dat")
        os.link(base + ".idx", twin + ".idx")
        ec.write_ec_files(twin, backend="numpy")
        ec.write_sorted_file_from_idx(twin)
        expected[vid] = twin
    bases = store_ec.generate_ec_shards_batch(store, [1, 2, 3],
                                              backend="numpy")
    for vid, base in bases.items():
        twin = expected[vid]
        for sid in range(TOTAL_SHARDS):
            assert filecmp.cmp(shard_file_name(base, sid),
                               shard_file_name(twin, sid),
                               shallow=False), f"vid {vid} shard {sid}"
        assert filecmp.cmp(base + ".ecx", twin + ".ecx", shallow=False)
        assert store.find_volume(vid).read_only  # frozen before encode
    store.close()


def test_generate_ec_shards_batch_unknown_vid(tmp_path):
    from seaweedfs_tpu.storage.needle import NeedleError
    from seaweedfs_tpu.storage.store import Store

    store = Store([str(tmp_path)])
    store.add_volume(1)
    with pytest.raises(NeedleError):
        store_ec.generate_ec_shards_batch(store, [1, 99], backend="numpy")
    # the whole list is validated BEFORE any volume is frozen: a bad
    # vid must not strand volume 1 read-only with no EC shards
    assert not store.find_volume(1).read_only
    store.close()


def test_parse_vid_list():
    from seaweedfs_tpu.shell.command_ec import parse_vid_list
    assert parse_vid_list("7") == [7]
    assert parse_vid_list("3,4,5") == [3, 4, 5]
    assert parse_vid_list("") == []
    assert parse_vid_list("0") == []  # 0 == unset, like the old flag
    with pytest.raises(ValueError):
        parse_vid_list("3,x")


def test_write_dat_file_backend_chunk_default(tmp_path):
    """Satellite: write_dat_file follows the backend's chunk default
    (no hardcoded DEFAULT_CHUNK) and still round-trips the .dat."""
    bases = _make_volumes(str(tmp_path), [3 * ROW + 250], seed=10)
    base = bases[0]
    with open(base + ".dat", "rb") as f:
        original = f.read()
    ec.write_ec_files(base, backend="numpy", large_block=LARGE,
                      small_block=SMALL, chunk=512)
    os.rename(base + ".dat", base + ".dat.orig")
    ec.write_dat_file(base, len(original), backend="numpy",
                      large_block=LARGE, small_block=SMALL)
    with open(base + ".dat", "rb") as f:
        assert f.read() == original


# --- the staging buffers (ISSUE 26: encode; ISSUE 28: rebuild; ISSUE 30: the
# result rows) ----------------------------------------------------------------
#
# Spans are read straight into the ten input rows of reused [14, lanes]
# buffers, and a jax dispatch's result lands in the rows after them. What
# that can break: a reused buffer holds an earlier dispatch's bytes (padding
# past EOF, or past a shard's end; an earlier, wider result), and a buffer
# handed out again while something still reads it.

@pytest.fixture(autouse=True)
def no_idle_staging(monkeypatch):
    """Every test starts as a new process would: no buffer left idle by
    an earlier pass (the idle list is the one thing passes share)."""
    monkeypatch.setattr(fleet, "_IDLE_STAGING", fleet._IdleStaging())


# volumes of more than 8 rows stay on the fleet's small-row path
ROOMY = 16 * LARGE


def _handed(state):
    from seaweedfs_tpu.stats.metrics import FleetStagingBuffersCounter
    return FleetStagingBuffersCounter.labels(state).value


def _landed(state):
    """Results of device dispatches by where they landed: `lent` (the
    result rows of a staging buffer) or `fresh` (an array a dispatch)."""
    from seaweedfs_tpu.stats.metrics import RsResultBuffersCounter
    return RsResultBuffersCounter.labels(state).value


def _two_passes(tmp_path, backend, sizes_by_pass, **kw):
    """Encode each list of sizes as one fleet pass, in this process and
    with one geometry, and hold every pass to the serial numpy encode.
    Returns (fresh, reused) handed out per pass."""
    counts = []
    for n, sizes in enumerate(sizes_by_pass):
        root = tmp_path / f"pass{n}"
        root.mkdir()
        bases = _make_volumes(str(root), sizes, seed=20 + n)
        twins = _serial_twin(bases)
        for t in twins:
            ec.write_ec_files(t, backend="numpy", large_block=ROOMY,
                              small_block=SMALL)
        before = _handed("fresh"), _handed("reused")
        fleet.fleet_write_ec_files(bases, backend=backend, large_block=ROOMY,
                                   small_block=SMALL, chunk=2 * ROW, **kw)
        counts.append((_handed("fresh") - before[0],
                       _handed("reused") - before[1]))
        _assert_shards_equal(bases, twins)
    return counts


LOST = (0, 3)                    # two data shards: a true inverse
# a rebuild's span rule floors spans at a small block; 128 lets volumes
# of a few KB be cut into many spans, two side by side in a buffer
REBUILD_FLOOR = 128
REBUILD_CHUNK = DATA_SHARDS * 2 * 700   # spans of ~700 bytes, two a buffer


def _encoded(root, sizes, seed):
    """Volumes of `sizes` with the serial numpy encoder's 14 files."""
    bases = _make_volumes(str(root), sizes, seed=seed)
    for base in bases:
        ec.write_ec_files(base, backend="numpy", large_block=ROOMY,
                          small_block=SMALL)
    return bases


def _lose_and_twin(root, sizes, seed):
    """Volumes of `sizes` encoded by the serial encoder, shards LOST
    removed; beside each a twin of the 12 survivors that the serial
    numpy rebuild has already repaired. Returns (bases, twins)."""
    bases = _encoded(root, sizes, seed)
    twins = []
    for base in bases:
        for sid in LOST:
            os.remove(shard_file_name(base, sid))
        twin = base + ".serial"
        for sid in range(TOTAL_SHARDS):
            if sid not in LOST:
                os.link(shard_file_name(base, sid),
                        shard_file_name(twin, sid))
        assert ec.rebuild_ec_files(twin, backend="numpy") == list(LOST)
        twins.append(twin)
    return bases, twins


def _rebuild_passes(tmp_path, monkeypatch, backend, sizes_by_pass, **kw):
    """Rebuild each list of sizes as one fleet pass (one group: every
    volume lost the same shards), in this process, and hold every pass
    to the serial numpy rebuild. Returns (fresh, reused) per pass."""
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    counts = []
    for n, sizes in enumerate(sizes_by_pass):
        root = tmp_path / f"rebuild{n}"
        root.mkdir()
        bases, twins = _lose_and_twin(root, sizes, 50 + n)
        before = _handed("fresh"), _handed("reused")
        rebuilt = fleet.fleet_rebuild_ec_files(bases, backend=backend,
                                               chunk=REBUILD_CHUNK, **kw)
        counts.append((_handed("fresh") - before[0],
                       _handed("reused") - before[1]))
        assert rebuilt == {b: list(LOST) for b in bases}
        _assert_shards_equal(bases, twins)
    return counts


def _passes(kind, tmp_path, monkeypatch, backend, sizes_by_pass, **kw):
    if kind == "rebuild":
        return _rebuild_passes(tmp_path, monkeypatch, backend,
                               sizes_by_pass, **kw)
    return _two_passes(tmp_path, backend, sizes_by_pass, **kw)


# first pass: every buffer full of random bytes; second: the ragged one
STALE = {
    # volumes whose last row is short (one of 700 bytes)
    "encode": ([6 * ROW, 6 * ROW, 6 * ROW],
               [3 * ROW + 123, 700, 2 * ROW + 1, ROW]),
    # a group of two volumes of unequal shard size (40 and 14 rows of
    # 256 bytes, spans of 683): the short one's last span, 169 bytes,
    # lies in a buffer beside a full span of the long one and after a
    # full one of its own, and neither shard is a multiple of the span
    "rebuild": ([40 * ROW, 40 * ROW], [40 * ROW, 13 * ROW + 77]),
}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("kind", sorted(STALE))
def test_stale_staging_bytes_never_reach_a_shard(tmp_path, monkeypatch,
                                                 kind, backend):
    """Two passes in one process: the first fills every staging buffer
    with full spans of random bytes, the second runs ragged volumes in
    the SAME buffers. The padding past EOF (encode) or past the shard's
    end (rebuild) must read as zeros, not as the first pass's data, and
    no byte of it may reach a file."""
    first, second = _passes(kind, tmp_path, monkeypatch, backend,
                            STALE[kind])
    assert first[0] > 0
    assert second == (0, second[1]) and second[1] > 0, \
        "the second pass did not run in the first pass's buffers"
    if kind == "rebuild":
        span, per_batch = fleet._stacked_spans(
            REBUILD_CHUNK, [40 * SMALL, 14 * SMALL])
        assert (span, per_batch) == (683, 2)
        assert (40 * SMALL) % span and (14 * SMALL) % span == 169
        assert second[1] > first[0]      # buffers came round within it


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("kind", sorted(STALE))
def test_stale_result_bytes_never_reach_a_shard(tmp_path, monkeypatch, kind,
                                                backend):
    """A dispatch's result lands in the last rows of its staging buffer,
    where the result of an earlier, wider dispatch still lies. The first
    pass fills every buffer with full spans; then every byte of every
    idle buffer — all 14 rows, the lanes no later dispatch uses too — is
    overwritten with a pattern, and the ragged pass, whose last
    dispatches are narrower, runs in them: what reaches the shard files
    is the serial encoder's bytes, none of the pattern's."""
    wide, ragged = STALE[kind]
    for n, sizes in enumerate((wide, ragged)):
        root = tmp_path / f"run{n}"
        root.mkdir()
        counts, = _passes(kind, root, monkeypatch, backend, [sizes])
        if n == 0:
            assert counts[0] > 0 and fleet._IDLE_STAGING._bufs
            for buf in fleet._IDLE_STAGING._bufs:
                assert buf.shape[0] == TOTAL_SHARDS
                buf[:] = 0xA5
    assert counts == (0, counts[1]) and counts[1] > 0, \
        "the second pass did not run in the first pass's buffers"


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("kind, sizes, first_counts, second_counts", [
    # 9 dispatches of 2 rows; share: 2 prefetched spans a buffer -> 2
    # buffers + 1, depth 2 + 1, one whose result is on the lanes
    ("encode", [9 * ROW, 9 * ROW], (7, 2), (0, 9)),
    # 15 spans of 683 a volume, two a buffer: 15 dispatches, same share
    ("rebuild", [40 * ROW, 40 * ROW], (7, 8), (0, 15)),
])
def test_second_pass_of_one_geometry_hands_out_no_fresh_buffer(
        tmp_path, monkeypatch, kind, sizes, first_counts, second_counts,
        backend):
    """The counter the benchmark's fleet_staging_reuse_share reads: a
    pass touches min(share, dispatches) buffers whatever the timing,
    and the next pass of the same geometry is handed only those. And
    the one rs_result_lent_share reads: every device dispatch of a pass
    puts its result into the buffer it was lent, none into an array of
    its own (a host codec's results are its own, and not counted)."""
    lent, fresh = _landed("lent"), _landed("fresh")
    first, second = _passes(kind, tmp_path, monkeypatch, backend,
                            [sizes, sizes], readers=2)
    assert first == first_counts
    assert second == second_counts
    assert _landed("fresh") == fresh
    assert _landed("lent") - lent == \
        (sum(first) + sum(second) if backend == "jax" else 0)


def test_short_pass_touches_only_the_buffers_it_fills(tmp_path):
    first, second = _two_passes(tmp_path, "numpy", [[3 * ROW], [3 * ROW]])
    assert first == (2, 0)           # 2 rows + 1 row: two dispatches
    assert second == (0, 2)


def _idle_shapes():
    return [b.shape for b in fleet._IDLE_STAGING._bufs]


def test_a_narrower_pass_borrows_the_idle_buffers(tmp_path, monkeypatch):
    """Buffers are kept by capacity (a width rounded up to a small
    block) and lent as [:, :lanes] views: a pass of a narrower geometry
    runs in the wider pass's buffers and leaves them as they were."""
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", 3 * SMALL)
    _two_passes(tmp_path, "numpy", [[4 * ROW]])          # 2 * SMALL lanes
    assert _idle_shapes() == [(TOTAL_SHARDS, 3 * SMALL)] * 2
    kept = [id(b) for b in fleet._IDLE_STAGING._bufs]
    bases = _make_volumes(str(tmp_path), [4 * ROW], seed=3)
    twins = _serial_twin(bases)
    ec.write_ec_files(twins[0], backend="numpy", large_block=ROOMY,
                      small_block=SMALL)
    fresh = _handed("fresh")
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=ROOMY,
                               small_block=SMALL, chunk=ROW)  # SMALL lanes
    _assert_shards_equal(bases, twins)
    # four dispatches of one row in the two buffers kept, two new ones
    # of the same capacity beside them
    assert _handed("fresh") - fresh == 2
    assert _idle_shapes() == [(TOTAL_SHARDS, 3 * SMALL)] * 4
    assert [id(b) for b in fleet._IDLE_STAGING._bufs][:2] == kept


def test_a_wider_pass_replaces_the_idle_buffers(tmp_path, monkeypatch):
    """One capacity at a time, the widest so far: what is idle and too
    narrow goes when a wider pass comes, so odd widths never pile up."""
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", SMALL)
    bases = _make_volumes(str(tmp_path), [4 * ROW], seed=3)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=ROOMY,
                               small_block=SMALL, chunk=ROW)
    assert _idle_shapes() == [(TOTAL_SHARDS, SMALL)] * 4
    first, = _two_passes(tmp_path, "numpy", [[4 * ROW]])  # 2 * SMALL lanes
    assert first == (2, 0)
    assert _idle_shapes() == [(TOTAL_SHARDS, 2 * SMALL)] * 2


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_alternating_encode_and_rebuild_passes_keep_their_buffers(
        tmp_path, monkeypatch, backend):
    """A server that encodes, rebuilds, encodes, rebuilds: the rebuild's
    width follows its largest shard (1,366 lanes here, an encode's 512),
    so its first pass makes the buffers wider, once. After that neither
    pass is handed a fresh buffer. On the jax backend a buffer reaches
    to its tail slab's end: 512 lanes are one whole slab of the (here
    shrunk) narrowest width, 1,366 a tail in a slab of 2,048."""
    from seaweedfs_tpu.ops import rs_kernel

    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    monkeypatch.setattr(rs_kernel, "_MIN_SLAB", 512)
    sizes = [40 * ROW, 40 * ROW]
    counts = []
    for n, kind in enumerate(["encode", "rebuild", "encode", "rebuild"]):
        root = tmp_path / f"round{n}"
        root.mkdir()
        counts += _passes(kind, root, monkeypatch, backend, [sizes])
    share = 7
    assert [fresh for fresh, _ in counts] == [share, share, 0, 0]
    assert _idle_shapes() == [
        (TOTAL_SHARDS, 2048 if backend == "jax" else 11 * REBUILD_FLOOR)
    ] * share


def test_read_span_into_zeroes_past_eof_on_every_use(tmp_path):
    """The reader of a volume's last span owns every lane of it: bytes
    past EOF are zeroed even where the file ends mid-block, at a block
    boundary, or before the span's second row."""
    rng = np.random.default_rng(31)
    for size in (700, SMALL, 3 * SMALL + 1, ROW, ROW + 5, 2 * ROW):
        base = str(tmp_path / f"v{size}")
        data = rng.integers(0, 256, size, dtype=np.uint8)
        with open(base + ".dat", "wb") as f:
            f.write(data.tobytes())
        buf = np.full((DATA_SHARDS, 5 * SMALL), 0xAB, dtype=np.uint8)
        fleet._read_span_into(base, 0, 2, ROW, SMALL, buf, SMALL)
        want = np.zeros(2 * ROW, dtype=np.uint8)
        want[:size] = data
        want = want.reshape(2, DATA_SHARDS, SMALL)
        for r in range(2):
            assert np.array_equal(
                buf[:, (1 + r) * SMALL:(2 + r) * SMALL], want[r]), (size, r)
        # lanes outside the span are somebody else's
        assert (buf[:, :SMALL] == 0xAB).all()
        assert (buf[:, 3 * SMALL:] == 0xAB).all()


@pytest.mark.parametrize("case, offset, sizes, want", [
    # the span ends inside the shard: all of it read
    ("inside", 100, [1000] * DATA_SHARDS, [300] * DATA_SHARDS),
    # the shard ends inside the span
    ("last_span", 900, [1000] * DATA_SHARDS, [100] * DATA_SHARDS),
    # nothing of the shard is left at this offset
    ("past_the_end", 1000, [1000] * DATA_SHARDS, [0] * DATA_SHARDS),
    # one survivor is shorter than the others say it should be
    ("short_survivor", 600, [1000] * 4 + [750] + [1000] * 5,
     [300] * 4 + [150] + [300] * 5),
])
def test_read_present_span_into_zeroes_past_the_shard_end_on_every_use(
        tmp_path, case, offset, sizes, want):
    """A rebuild or verify span's reader owns every lane of its span in
    a dirty buffer: what it does not fill from a survivor it zeroes, and it
    touches no lane of another span."""
    rng = np.random.default_rng(35)
    base, span, off = str(tmp_path / "v"), 300, 200
    present = [1, 2, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
    data = {}
    for sid, size in zip(present, sizes):
        data[sid] = rng.integers(1, 256, size, dtype=np.uint8)
        with open(shard_file_name(base, sid), "wb") as f:
            f.write(data[sid].tobytes())
    buf = np.full((DATA_SHARDS, 4 * span), 0xAB, dtype=np.uint8)
    for _ in range(2):                   # every use, not the first alone
        buf[:, off:off + span] = 0xCD
        fleet._read_present_span_into(base, present, sizes[0], offset, span,
                                      buf, off)
        for row, sid in enumerate(present[:DATA_SHARDS]):
            n = want[row]
            assert np.array_equal(buf[row, off:off + n],
                                  data[sid][offset:offset + n]), (case, row)
            assert not buf[row, off + n:off + span].any(), (case, row)
        assert (buf[:, :off] == 0xAB).all()
        assert (buf[:, off + span:] == 0xAB).all()


@pytest.mark.parametrize("iov_max, most", [(3, None), (1024, 100), (7, 33)])
def test_preadv_full_survives_iov_max_and_short_reads(tmp_path, monkeypatch,
                                                      iov_max, most):
    """A span is rows * 10 iovecs — past IOV_MAX at small blocks — and
    the kernel may cut any read short: the shards stay byte-identical."""
    monkeypatch.setattr(fleet, "_IOV_MAX", iov_max)
    calls = []
    real = os.preadv

    def preadv(fd, views, offset):
        calls.append(len(views))
        if most is None:
            return real(fd, views, offset)
        # the kernel's cut: only the first `most` bytes arrive
        cut, room = [], most
        for v in views:
            if room <= 0:
                break
            cut.append(v[:room])
            room -= len(cut[-1])
        return real(fd, cut, offset)

    monkeypatch.setattr(os, "preadv", preadv)
    bases = _make_volumes(str(tmp_path), [5 * ROW + 300, 700], seed=32)
    twins = _serial_twin(bases)
    for t in twins:
        ec.write_ec_files(t, backend="numpy", large_block=ROOMY,
                          small_block=SMALL)
    fleet.fleet_write_ec_files(bases, backend="numpy", large_block=ROOMY,
                               small_block=SMALL, chunk=4 * ROW)
    _assert_shards_equal(bases, twins)
    assert max(calls) <= iov_max


def test_staging_buffer_is_free_only_after_result_and_every_write():
    """The release rule, alone: a buffer with a dispatch and two spans
    comes round again only when all three readers are through."""
    import threading

    st = fleet._Staging(64, 3, lambda: None)
    try:
        batches = [fleet._StagedBatch(st.acquire()) for _ in range(3)]
        for b in batches:
            b.refs = 3
        got = []
        t = threading.Thread(target=lambda: got.append(st.acquire()),
                             daemon=True)
        t.start()
        st.unref(batches[1])
        st.unref(batches[1])
        t.join(0.3)
        assert t.is_alive() and not got, "handed out with a reader left"
        st.unref(batches[1])
        t.join(5)
        assert not t.is_alive() and got[0] is batches[1].buf
    finally:
        st.close()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("kind, sizes, dispatches, writes", [
    # every span's data-shard write reads the buffer, and its parity
    # write too
    ("encode", [5 * ROW + 1, 3 * ROW, 700], 5, 2 * (6 + 3 + 1)),
    # every span's rebuilt shards are written out of the buffer
    ("rebuild", [40 * ROW, 13 * ROW + 77], 11, 15 + 6),
])
def test_buffer_readers_are_the_retire_thread_and_the_writer_lanes(
        tmp_path, monkeypatch, kind, sizes, dispatches, writes, backend):
    """Who lets go of a buffer, and where: the retire thread once a
    dispatch, when it has the result (every transfer out of the buffer
    is over), and a writer lane once for every write of a span: its
    parity or its rebuilt shards, and, in an encode pass, its data
    shards. Nothing is released from the packing thread, so a buffer is
    never free before its result is on the host."""
    import threading

    by_thread = []
    real = fleet._Staging.unref

    def unref(self, batch):
        by_thread.append(threading.current_thread().name)
        real(self, batch)

    monkeypatch.setattr(fleet._Staging, "unref", unref)
    first, = _passes(kind, tmp_path, monkeypatch, backend, [sizes])
    assert int(sum(first)) == dispatches
    assert by_thread.count("fleet-retire") == dispatches
    assert len([t for t in by_thread
                if t.startswith("fleet-write-")]) == writes
    assert len(by_thread) == dispatches + writes


def test_staging_acquire_raises_the_latched_error_instead_of_waiting():
    """After a pipeline error the closures that release buffers are
    skipped: a packing thread waiting for one must see the error."""
    def check():
        raise OSError("disk full")

    st = fleet._Staging(64, 2, check)
    try:
        st.acquire(), st.acquire()
        with pytest.raises(OSError, match="disk full"):
            st.acquire()
    finally:
        st.close()


def test_buffer_is_not_handed_out_before_its_data_shard_writes(tmp_path,
                                                               monkeypatch):
    """With the writer lane held back on the first data-shard write,
    the scheduler runs out of buffers and WAITS: the buffer under that
    write is not handed to a reader until the write has run — and the
    shards come out byte-identical once it has."""
    import threading
    import time

    gate = threading.Event()
    handed, held = [], []
    real_acquire = fleet._Staging.acquire
    real_write = fleet._write_data_shards

    def acquire(self):
        buf = real_acquire(self)
        handed.append(id(buf.base))      # lent as a view of the kept one
        return buf

    def write(base, arr, done):
        if not held:
            held.append(id(arr.base))
            gate.wait(30)
        real_write(base, arr, done)

    monkeypatch.setattr(fleet._Staging, "acquire", acquire)
    monkeypatch.setattr(fleet, "_write_data_shards", write)
    bases = _make_volumes(str(tmp_path), [24 * ROW + 9], seed=33)
    twins = _serial_twin(bases)
    ec.write_ec_files(twins[0], backend="numpy", large_block=ROOMY,
                      small_block=SMALL)
    errors = []

    def run():
        try:
            fleet.fleet_write_ec_files(
                bases, backend="numpy", large_block=ROOMY, small_block=SMALL,
                chunk=2 * ROW, readers=1, depth=1)
        except BaseException as e:  # surfaced below
            errors.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    share = 2 + 1 + 1 + 1 + 1        # 2 prefetched spans, one a buffer
    deadline = time.monotonic() + 10
    while len(handed) < share and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.3)                  # room for a wrong hand-out
    try:
        assert len(handed) == share and len(set(handed)) == share
        assert handed.count(held[0]) == 1
    finally:
        gate.set()
        t.join(30)
    assert not t.is_alive() and not errors
    assert len(handed) == 13 and handed.count(held[0]) >= 2
    _assert_shards_equal(bases, twins)


def test_rebuild_buffer_is_not_handed_out_before_its_result(tmp_path,
                                                            monkeypatch):
    """With the first dispatch's compute held back, the pass fills what
    the pipeline holds up to the retire thread and WAITS: the buffer
    under that dispatch goes to no reader until the retire thread has
    its result — and the rebuilt shards come out byte-identical once it
    has."""
    import threading
    import time

    gate = threading.Event()
    handed, held = [], []
    real_acquire = fleet._Staging.acquire
    real_apply = ReedSolomon.reconstruct_some

    def acquire(self):
        buf = real_acquire(self)
        handed.append(id(buf.base))
        return buf

    def apply(self, present, wanted, shard_data):
        if not held:
            held.append(id(shard_data.base))
            gate.wait(30)
        return real_apply(self, present, wanted, shard_data)

    monkeypatch.setattr(fleet._Staging, "acquire", acquire)
    monkeypatch.setattr(ReedSolomon, "reconstruct_some", apply)
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases, twins = _lose_and_twin(tmp_path, [40 * ROW, 40 * ROW], 36)
    errors = []

    def run():
        try:
            fleet.fleet_rebuild_ec_files(bases, backend="numpy",
                                         chunk=REBUILD_CHUNK, readers=1,
                                         depth=1)
        except BaseException as e:  # surfaced below
            errors.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # 4 prefetched spans, two a buffer; with no result yet none is on
    # the lanes, so the share's last buffer stays where it is
    busy = 2 + 1 + 1 + 1
    deadline = time.monotonic() + 10
    while len(handed) < busy and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.3)                  # room for a wrong hand-out
    try:
        assert len(handed) == busy and len(set(handed)) == busy
        assert handed.count(held[0]) == 1
    finally:
        gate.set()
        t.join(30)
    assert not t.is_alive() and not errors
    assert len(handed) == 15 and handed.count(held[0]) >= 2
    _assert_shards_equal(bases, twins)


@pytest.mark.parametrize("kind, dispatches", [
    ("encode", 13), ("rebuild", 15)])
def test_buffer_is_not_handed_out_while_a_lane_reads_its_result(
        tmp_path, monkeypatch, kind, dispatches):
    """The result rows have a reader of their own: the parity write
    (encode), the rebuilt-shard write (rebuild), each on a writer lane
    (a verify's lanes read nothing of the buffer: the test after this
    one). With the first of them held back the pass
    runs out of buffers and WAITS: the buffer whose result that closure
    reads goes to no reader, and no other buffer of the share comes
    round before it (their closures queue behind the held one) — and
    the output is right once it has run."""
    import threading
    import time

    gate = threading.Event()
    handed, held = [], []
    real_acquire = fleet._Staging.acquire
    real_then = fleet._then_release

    def acquire(self):
        buf = real_acquire(self)
        handed.append(id(buf.base))      # lent as a view of the kept one
        return buf

    def then_release(fn, release):
        run = real_then(fn, release)

        def held_back(out):
            if not held:
                # jax: one span's lanes of the buffer's result rows
                held.append(id(out.base))
                gate.wait(30)
            run(out)
        return held_back

    monkeypatch.setattr(fleet._Staging, "acquire", acquire)
    monkeypatch.setattr(fleet, "_then_release", then_release)
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    if kind == "encode":
        bases = _make_volumes(str(tmp_path), [24 * ROW + 9], seed=37)
        twins = _serial_twin(bases)
        ec.write_ec_files(twins[0], backend="numpy", large_block=ROOMY,
                          small_block=SMALL)
        run = functools.partial(
            fleet.fleet_write_ec_files, bases, backend="jax",
            large_block=ROOMY, small_block=SMALL, chunk=2 * ROW, readers=1,
            depth=1)
    else:
        bases, twins = _lose_and_twin(tmp_path, [40 * ROW, 40 * ROW], 38)
        run = functools.partial(
            fleet.fleet_rebuild_ec_files, bases, backend="jax",
            chunk=REBUILD_CHUNK, readers=1, depth=1)
    errors = []

    def guarded():
        try:
            run()
        except BaseException as e:  # surfaced below
            errors.append(e)

    t = threading.Thread(target=guarded, daemon=True)
    t.start()
    share = 2 + 1 + 1 + 1 + 1
    # until the pass stands still: nothing handed out for a while
    deadline = time.monotonic() + 30
    seen, quiet = -1, 0
    while quiet < 10 and time.monotonic() < deadline:
        time.sleep(0.05)
        quiet = quiet + 1 if held and len(handed) == seen else 0
        seen = len(handed)
    try:
        assert held and t.is_alive()
        assert len(handed) <= share and len(set(handed)) == len(handed)
        assert handed.count(held[0]) == 1
    finally:
        gate.set()
        t.join(30)
    assert not t.is_alive() and not errors
    assert len(handed) == dispatches and handed.count(held[0]) >= 2
    _assert_shards_equal(bases, twins)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_verify_buffer_is_free_once_its_input_is_read(tmp_path, monkeypatch,
                                                      backend):
    """What a verify's writer lanes read is not in the staging buffer:
    a host codec's own parity against the parity files, or the device's
    counts. So with the first span's closure held back on its lane the
    pass never waits for a BUFFER: it goes on handing out the share's
    buffers, the held span's own among them, until the full lane stops
    the retire thread — and the result is right once the lane runs."""
    import threading
    import time

    gate = threading.Event()
    handed, held = [], []
    real_acquire = fleet._Staging.acquire
    real_submit = fleet.TaggedPipeline.submit

    def acquire(self):
        buf = real_acquire(self)
        handed.append(id(buf.base))
        return buf

    def held_back(fn):
        def run(out):
            if not held:
                held.append(len(handed))
                gate.wait(30)
            fn(out)
        return run

    def submit(self, handle, tagged, timeout_s=None):
        real_submit(self, handle,
                    [(tag, held_back(fn)) for tag, fn in tagged], timeout_s)

    monkeypatch.setattr(fleet._Staging, "acquire", acquire)
    monkeypatch.setattr(fleet.TaggedPipeline, "submit", submit)
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases = _encoded(tmp_path, [40 * ROW, 40 * ROW], 39)
    _flip(bases[1], 11, 2000)
    verified, errors = {}, []

    def guarded():
        try:
            verified.update(fleet.fleet_verify_ec_files(
                bases, backend=backend, chunk=REBUILD_CHUNK, readers=1,
                depth=1))
        except BaseException as e:  # surfaced below
            errors.append(e)

    t = threading.Thread(target=guarded, daemon=True)
    t.start()
    share = 2 + 1 + 1 + 1 + 1
    deadline = time.monotonic() + 30
    seen, quiet = -1, 0
    while quiet < 10 and time.monotonic() < deadline:
        time.sleep(0.05)
        quiet = quiet + 1 if held and len(handed) == seen else 0
        seen = len(handed)
    try:
        assert held and t.is_alive()
        # more hand-outs than the share has buffers: they came round
        # while the closure of the first dispatch stood on its lane
        assert len(handed) > share and len(set(handed)) <= share
        assert handed.count(handed[0]) >= 2
    finally:
        gate.set()
        t.join(30)
    assert not t.is_alive() and not errors
    assert len(handed) == 15
    assert verified[bases[0]].clean and verified[bases[0]].spans == 15
    assert verified[bases[1]].parity_mismatch == {11: 1}
    assert verified[bases[1]].first_mismatch == {11: 2000}


@pytest.mark.parametrize("kind", ["encode", "rebuild"])
def test_failed_pass_returns_every_staging_buffer(tmp_path, monkeypatch,
                                                  kind):
    """An error latched in the pipeline skips the closures that release
    buffers: the packing thread, out of buffers, is told the error and
    does not wait; the pass still gives back every buffer it filled (a
    rebuild pass unlinks its outputs besides), and the next pass of the
    geometry runs in them."""
    writer = {"encode": "_write_parity_span",
              "rebuild": "_write_rebuilt_span"}[kind]
    real = getattr(fleet, writer)
    seen = []

    def failing(base, *args):
        seen.append(base)
        if len(seen) == 3:
            raise OSError("no space left on device")
        real(base, *args)

    def run(bases):
        if kind == "encode":
            fleet.fleet_write_ec_files(bases, backend="numpy",
                                       large_block=ROOMY, small_block=SMALL,
                                       chunk=2 * ROW)
        else:
            fleet.fleet_rebuild_ec_files(bases, backend="numpy",
                                         chunk=REBUILD_CHUNK)

    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    sizes = {"encode": [20 * ROW, 20 * ROW], "rebuild": [54 * ROW] * 2}[kind]
    if kind == "encode":
        bases = _make_volumes(str(tmp_path), sizes, seed=34)
        twins = _serial_twin(bases)
        for t in twins:
            ec.write_ec_files(t, backend="numpy", large_block=ROOMY,
                              small_block=SMALL)
    else:
        bases, twins = _lose_and_twin(tmp_path, sizes, 34)
    monkeypatch.setattr(fleet, writer, failing)
    fresh0 = _handed("fresh")
    with pytest.raises(OSError, match="no space left"):
        run(bases)
    made = int(_handed("fresh") - fresh0)
    assert made >= 2
    assert len(fleet._IDLE_STAGING._bufs) == made
    if kind == "rebuild":
        for base in bases:
            for sid in LOST:
                assert not os.path.exists(shard_file_name(base, sid))
    monkeypatch.setattr(fleet, writer, real)
    fresh1, reused1 = _handed("fresh"), _handed("reused")
    run(bases)
    _assert_shards_equal(bases, twins)
    share = 2 + 1 + 2 + 1 + 1        # 4 prefetched spans, two a buffer
    assert _handed("fresh") - fresh1 == share - made
    assert _handed("reused") - reused1 == 20 - (share - made)


def test_concurrent_passes_share_nothing_but_the_idle_list(tmp_path,
                                                          monkeypatch):
    """Several schedulers at once in one process (one a device, parallel
    generate and rebuild RPCs), of two widths: each has its own share,
    none waits for another's buffers, all stay byte-identical, and what
    they leave idle is of one capacity."""
    import sys
    import threading

    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    jobs = []
    for n in range(8):
        root = tmp_path / f"job{n}"
        root.mkdir()
        if n % 2:
            bases, twins = _lose_and_twin(
                root, [20 * ROW + n, 7 * ROW, 700 + n], 40 + n)
            run = functools.partial(fleet.fleet_rebuild_ec_files, bases,
                                    backend="numpy", chunk=REBUILD_CHUNK)
        else:
            bases = _make_volumes(str(root), [7 * ROW + n, 3 * ROW, 700 + n],
                                  seed=40 + n)
            twins = _serial_twin(bases)
            for t in twins:
                ec.write_ec_files(t, backend="numpy", large_block=ROOMY,
                                  small_block=SMALL)
            run = functools.partial(fleet.fleet_write_ec_files, bases,
                                    backend="numpy", large_block=ROOMY,
                                    small_block=SMALL, chunk=2 * ROW)
        jobs.append((bases, twins, run))
    errors = []

    def guarded(run):
        try:
            run()
        except BaseException as e:  # surfaced below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=guarded, args=(run,), daemon=True)
                   for _, _, run in jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    for bases, twins, _ in jobs:
        _assert_shards_equal(bases, twins)
    assert len(fleet._IDLE_STAGING._bufs) <= 8
    assert len(set(_idle_shapes())) <= 1


# --- verify on the staged loop ------------------------------------------------
# The scrub's pass is a plan and a flush for `_staged_pass`, like encode
# and rebuild: same span rule as rebuild, same reused buffers, the same
# 2-D view into the dispatch layer.

VERIFY_FIELDS = ("parity_mismatch", "first_mismatch", "missing",
                 "parity_checked", "bytes_verified", "verified")


def _flip(base, sid, offset):
    _flip_byte(shard_file_name(base, sid), offset)


def _plain_verify(base):
    """The reference: whole shards, one numpy encode, one compare. A
    parity file that ends early differs in every byte it lacks; a volume
    without all ten data shards is not verified."""
    shards = {sid: np.fromfile(shard_file_name(base, sid), dtype=np.uint8)
              for sid in range(TOTAL_SHARDS)
              if os.path.exists(shard_file_name(base, sid))}
    want = {"missing": [s for s in range(TOTAL_SHARDS) if s not in shards],
            "parity_checked": [], "parity_mismatch": {}, "first_mismatch": {},
            "bytes_verified": 0, "verified": False}
    if any(s < DATA_SHARDS for s in want["missing"]):
        return want
    want.update(verified=True,
                parity_checked=[s for s in shards if s >= DATA_SHARDS],
                bytes_verified=DATA_SHARDS * len(shards[0]))
    parity = ReedSolomon(backend="numpy").encode(
        np.stack([shards[i] for i in range(DATA_SHARDS)]))
    for sid in want["parity_checked"]:
        have = len(shards[sid])
        diff = np.nonzero(parity[sid - DATA_SHARDS, :have] != shards[sid])[0]
        lacking = parity.shape[1] - have
        if len(diff) or lacking:
            want["parity_mismatch"][sid] = len(diff) + lacking
            want["first_mismatch"][sid] = int(diff[0]) if len(diff) else have
    return want


@pytest.mark.parametrize("backend", ["numpy", "native", "jax"])
def test_verify_equals_serial_on_every_backend(tmp_path, monkeypatch,
                                               backend):
    """Three volumes of unequal size — one shorter than a span, one not
    a multiple of it — over several buffers, the shorter ones' last
    spans beside spans of a later round: every field of the result but
    `spans` is what a plain whole-shard compare finds."""
    if backend == "native":
        from seaweedfs_tpu.native import rs_native
        if not rs_native.available():
            pytest.skip("native lib not built")
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases = _encoded(tmp_path, [40 * ROW, 13 * ROW + 77, 200], 60)
    sizes = [os.path.getsize(shard_file_name(b, 0)) for b in bases]
    span, per_batch = fleet._stacked_spans(REBUILD_CHUNK, sizes)
    assert (span, per_batch) == (466, 3)
    assert sizes[2] < span and sizes[1] % span and sizes[0] > 3 * span
    _flip(bases[0], 12, 5 * span + 17)         # a parity shard
    _flip(bases[0], 12, 5 * span + 18)
    _flip(bases[1], 3, sizes[1] - 1)           # a data shard's last byte
    got = fleet.fleet_verify_ec_files(bases, backend=backend,
                                      chunk=REBUILD_CHUNK)
    for base, size in zip(bases, sizes):
        want = _plain_verify(base)
        assert {f: getattr(got[base], f) for f in VERIFY_FIELDS} == want
        assert got[base].spans == -(-size // span)
    assert got[bases[0]].parity_mismatch == {12: 2}
    assert sorted(got[bases[1]].parity_mismatch) == [10, 11, 12, 13]
    assert got[bases[2]].clean


@pytest.mark.parametrize("case", ["parity-last-lane", "parity-first-lane",
                                  "data-shard"])
def test_verify_finds_damage_on_span_and_buffer_edges(tmp_path, monkeypatch,
                                                      case):
    """Two volumes, two spans a buffer: volume 0's span k lies in lanes
    [0, span) of buffer k, volume 1's in [span, 2 * span). One flipped
    byte on an edge is found at its offset, once, in the right shard."""
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases = _encoded(tmp_path, [40 * ROW, 40 * ROW], 61)
    span, per_batch = fleet._stacked_spans(REBUILD_CHUNK, [40 * SMALL] * 2)
    assert (span, per_batch) == (683, 2)
    hit, sid, offset = {
        # the last lane of a span that another volume's span follows
        "parity-last-lane": (0, 11, span - 1),
        # the first lane of the next buffer
        "parity-first-lane": (0, 12, span),
        # the last lane of a buffer, in a data shard: all four parity
        # shards disagree there
        "data-shard": (1, 4, 2 * span - 1),
    }[case]
    _flip(bases[hit], sid, offset)
    got = fleet.fleet_verify_ec_files(bases, backend="numpy",
                                      chunk=REBUILD_CHUNK)
    shards = [sid] if sid >= DATA_SHARDS else [10, 11, 12, 13]
    assert got[bases[hit]].parity_mismatch == {s: 1 for s in shards}
    assert got[bases[hit]].first_mismatch == {s: offset for s in shards}
    assert got[bases[1 - hit]].clean
    assert all(r.spans == 15 and r.bytes_verified == 400 * ROW // 10
               for r in got.values())


def test_verify_dispatches_views_of_reused_staging(tmp_path, monkeypatch):
    """A scrub after an encode of the same width makes no staging
    buffer: every buffer it is handed is one the encode pass filled, and
    what reaches the dispatch layer is a 2-D view of ALL 14 rows of one
    of them — the stored parity beside the data and the buffer's slack
    up to the tail slab's end, no stacked copy, no memory lent for a
    result."""
    from seaweedfs_tpu.ops import rs_kernel

    block = rs_kernel.VERIFY_BLOCK
    room = rs_kernel.placed_lanes(3 * block)
    assert room == rs_kernel._MIN_SLAB > 3 * block
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    rows = 7 * 3 * block // (3 * SMALL)        # 7 dispatches of 3 spans
    bases = _make_volumes(str(tmp_path), [rows * ROW] * 3, seed=62)
    fleet.fleet_write_ec_files(bases, backend="jax", large_block=ROOMY,
                               small_block=SMALL,
                               chunk=3 * block // SMALL * ROW)
    idle = list(fleet._IDLE_STAGING._bufs)
    assert len(idle) == 7 and \
        _idle_shapes() == [(TOTAL_SHARDS, room)] * 7
    seen = []
    real = rs_kernel.verify_stripe_async

    def recording(matrix, stripe, device=None, lanes=None):
        seen.append((stripe, lanes))
        return real(matrix, stripe, device=device, lanes=lanes)

    monkeypatch.setattr(rs_kernel, "verify_stripe_async", recording)
    monkeypatch.setattr(
        rs_kernel, "apply_matrix_async",
        lambda *a, **kw: pytest.fail("a verify fetched parity"))
    fresh, reused, lent = _handed("fresh"), _handed("reused"), _landed("lent")
    chunk = DATA_SHARDS * 3 * block
    span, per_batch = fleet._stacked_spans(chunk, [rows * SMALL] * 3)
    assert (span, per_batch) == (block, 3)     # 21 spans, 7 dispatches
    got = fleet.fleet_verify_ec_files(bases, backend="jax", chunk=chunk)
    assert all(r.clean and r.spans == 7 for r in got.values())
    assert _handed("fresh") == fresh
    assert _handed("reused") - reused == 7
    assert _landed("lent") == lent
    assert [id(b) for b in fleet._IDLE_STAGING._bufs] == \
        [id(b) for b in idle]
    assert len(seen) == 7
    for stripe, lanes in seen:
        assert lanes == per_batch * span
        assert stripe.shape == (TOTAL_SHARDS, room)
        assert sum(np.shares_memory(stripe, b) for b in idle) == 1


@pytest.mark.parametrize("n", [1, 2, 128])
def test_verify_span_rule_is_the_rebuild_rule(tmp_path, monkeypatch, n):
    """`chunk` means in a verify pass what it means in the other two:
    the input bytes of all ten rows of one fused dispatch. Files of
    zeros are a valid stripe, so the pass runs and finds them clean."""
    small_block, size = 512, 1280
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", small_block)
    chunk = DATA_SHARDS * 4 * small_block
    bases = [str(tmp_path / f"{v}") for v in range(n)]
    for base in bases:
        for sid in range(TOTAL_SHARDS):
            with open(shard_file_name(base, sid), "wb") as f:
                f.truncate(size)
    span, per_batch = fleet._stacked_spans(chunk, [size] * n)
    assert (span, per_batch) == {1: (1280, 1), 2: (640, 2),
                                 128: (427, 4)}[n]
    batches = []
    encode_lanes = fleet._Dispatcher.encode_lanes

    def counted(self, buf, cuts, done):
        batches.append(cuts)
        return encode_lanes(self, buf, cuts, done)

    monkeypatch.setattr(fleet._Dispatcher, "encode_lanes", counted)
    got = fleet.fleet_verify_ec_files(bases, backend="numpy", chunk=chunk)
    per_volume = -(-size // span)
    assert all(r.clean and r.spans == per_volume for r in got.values())
    assert [len(cuts) for cuts in batches] == \
        [per_batch] * (n * per_volume // per_batch)
    assert {w for cuts in batches for _, w in cuts} == {span}
    assert all([off for off, _ in cuts] ==
               [i * span for i in range(per_batch)] for cuts in batches)


def test_verify_paces_the_throttler_per_span(tmp_path, monkeypatch):
    """The read side is paced once a planned span, by the ten data
    reads and the parity reads its compare will make — three for a
    volume that lost a parity shard."""
    class Counting:
        def __init__(self):
            self.seen = []

        def maybe_slowdown(self, n):
            self.seen.append(n)

    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases = _encoded(tmp_path, [40 * ROW, 40 * ROW], 63)
    os.remove(shard_file_name(bases[1], 13))
    throttler = Counting()
    got = fleet.fleet_verify_ec_files(bases, backend="numpy",
                                      chunk=REBUILD_CHUNK,
                                      throttler=throttler)
    span = 683
    assert throttler.seen == [14 * span, 13 * span] * 15
    assert got[bases[0]].clean and got[bases[0]].spans == 15
    assert got[bases[1]].missing == [13] and got[bases[1]].verified
    assert got[bases[1]].parity_checked == [10, 11, 12]
    assert not got[bases[1]].parity_mismatch and got[bases[1]].spans == 15


def test_verify_failed_pass_leaks_nothing(tmp_path, monkeypatch):
    """A dispatch that fails fails the call, and the call leaves nothing
    behind: the parity files are closed, the buffers it filled are on
    the idle list, and the next pass runs in them."""
    from seaweedfs_tpu.resilience import failpoint

    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases = _encoded(tmp_path, [40 * ROW, 40 * ROW], 64)
    open_fds = len(os.listdir("/proc/self/fd"))
    fresh = _handed("fresh")
    failpoint.arm("fleet.dispatch", "error", match={"op": "encode"})
    try:
        with pytest.raises(failpoint.FailpointError):
            fleet.fleet_verify_ec_files(bases, backend="numpy",
                                        chunk=REBUILD_CHUNK)
    finally:
        failpoint.disarm("fleet.dispatch")
    assert len(os.listdir("/proc/self/fd")) == open_fds
    made = int(_handed("fresh") - fresh)
    assert made >= 1 and len(fleet._IDLE_STAGING._bufs) == made
    idle = [id(b) for b in fleet._IDLE_STAGING._bufs]
    got = fleet.fleet_verify_ec_files(bases, backend="numpy",
                                      chunk=REBUILD_CHUNK)
    assert all(r.clean and r.spans == 15 for r in got.values())
    assert [id(b) for b in fleet._IDLE_STAGING._bufs][:made] == idle
    assert len(os.listdir("/proc/self/fd")) == open_fds


# --- verify on the device: counts come back, not parity -----------------------

def _truncate(base, sid, size):
    with open(shard_file_name(base, sid), "r+b") as f:
        f.truncate(size)


DAMAGE = {
    # case -> (volume sizes, damage(bases, shard sizes, span))
    "clean": ([40 * ROW, 40 * ROW], lambda b, z, span: None),
    "parity-flip": ([40 * ROW, 40 * ROW], lambda b, z, span: (
        _flip(b[0], 12, 5 * span + 17), _flip(b[0], 12, 5 * span + 18),
        _flip(b[1], 10, 0))),
    # all four parity rows disagree, at the same offsets
    "data-flip": ([40 * ROW, 40 * ROW], lambda b, z, span: (
        _flip(b[1], 3, 2 * span - 1), _flip(b[1], 3, 2 * span))),
    "truncated-parity": ([40 * ROW, 40 * ROW], lambda b, z, span: (
        _truncate(b[0], 11, 3 * span + 9), _flip(b[0], 11, 77),
        _flip(b[1], 13, 5))),
    "missing-parity-shard": ([40 * ROW, 40 * ROW], lambda b, z, span: (
        os.remove(shard_file_name(b[0], 13)), _flip(b[0], 10, 4 * span))),
    "missing-data-shard": ([40 * ROW, 40 * ROW], lambda b, z, span: (
        os.remove(shard_file_name(b[1], 6)), _flip(b[0], 11, 1))),
    # three volumes share every buffer; one is shorter than a span, one
    # not a multiple of it
    "unequal-volumes": ([40 * ROW, 13 * ROW + 77, 200], lambda b, z, span: (
        _flip(b[0], 12, z[0] - 1), _flip(b[1], 3, z[1] - 1),
        _flip(b[2], 10, 0))),
    # the last span of a volume is short: damage in its last lanes
    "last-short-span": ([33 * ROW + 100, 40 * ROW], lambda b, z, span: (
        _flip(b[0], 13, z[0] - 1), _flip(b[0], 13, z[0] - z[0] % span))),
}


@pytest.mark.parametrize("case", sorted(DAMAGE))
def test_device_verify_equals_host_verify_and_a_plain_compare(
        tmp_path, monkeypatch, case):
    """`fleet_verify_ec_files(backend="jax")`, which places the stored
    parity beside the data and fetches counts, against the numpy backend
    (the host compare on the writer lanes) and against a plain numpy
    re-encode of whole shards: byte count for byte count, offset for
    offset, in every field of the result."""
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    sizes, damage = DAMAGE[case]
    bases = _encoded(tmp_path, sizes, 80 + sorted(DAMAGE).index(case))
    shard_sizes = [os.path.getsize(shard_file_name(b, 0)) for b in bases]
    span, _ = fleet._stacked_spans(REBUILD_CHUNK, shard_sizes)
    if case == "last-short-span":
        assert shard_sizes[0] % span
    damage(bases, shard_sizes, span)
    want = {b: _plain_verify(b) for b in bases}
    assert case == "clean" or any(
        w["parity_mismatch"] or not w["verified"] for w in want.values())
    host = fleet.fleet_verify_ec_files(bases, backend="numpy",
                                       chunk=REBUILD_CHUNK)
    device = fleet.fleet_verify_ec_files(bases, backend="jax",
                                         chunk=REBUILD_CHUNK)
    for base in bases:
        for got in (host[base], device[base]):
            assert {f: getattr(got, f) for f in VERIFY_FIELDS} == want[base]
        if want[base]["verified"]:
            # a volume with a short parity file is held to its files by
            # a host codec, in a pass of its own width
            assert device[base].spans == host[base].spans or \
                case == "truncated-parity"
    if case == "data-flip":
        assert device[bases[1]].parity_mismatch == \
            {10: 2, 11: 2, 12: 2, 13: 2}
        assert set(device[bases[1]].first_mismatch.values()) == {2 * span - 1}


def _verified_bytes(where):
    from seaweedfs_tpu.stats.metrics import FleetVerifyBytesCounter
    return FleetVerifyBytesCounter.labels(where).value


@pytest.mark.parametrize("backend, where, other", [
    ("jax", "device", "host"), ("numpy", "host", "device")])
def test_verify_counts_its_bytes_where_the_compare_ran(tmp_path, monkeypatch,
                                                       backend, where, other):
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases = _encoded(tmp_path, [40 * ROW, 13 * ROW + 77], 95)
    before = _verified_bytes(where), _verified_bytes(other)
    got = fleet.fleet_verify_ec_files(bases, backend=backend,
                                      chunk=REBUILD_CHUNK)
    assert _verified_bytes(where) - before[0] == \
        sum(r.bytes_verified for r in got.values()) == \
        DATA_SHARDS * sum(os.path.getsize(shard_file_name(b, 0))
                          for b in bases)
    assert _verified_bytes(other) == before[1]


def test_device_verify_reads_fourteen_rows_and_lends_nothing(tmp_path,
                                                             monkeypatch):
    """What a jax verify fetches is counts: every dispatch's `rs.fetch`
    is a few hundred bytes a slab, no result lands anywhere, and its
    placement carries all 14 rows."""
    from seaweedfs_tpu.ops import rs_kernel

    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    bases = _encoded(tmp_path, [40 * ROW, 40 * ROW], 96)
    phases = []
    real = rs_kernel._phase

    def phase(name, **tags):
        phases.append((name, tags.get("bytes")))
        return real(name, **tags)

    monkeypatch.setattr(rs_kernel, "_phase", phase)
    lent, fresh = _landed("lent"), _landed("fresh")
    got = fleet.fleet_verify_ec_files(bases, backend="jax",
                                      chunk=REBUILD_CHUNK)
    assert all(r.clean and r.spans == 15 for r in got.values())
    assert (_landed("lent"), _landed("fresh")) == (lent, fresh)
    placed = [b for name, b in phases if name == "place"]
    fetched = [b for name, b in phases if name == "fetch"]
    assert len(placed) == len(fetched) == 15
    assert set(placed) == {TOTAL_SHARDS * rs_kernel._MIN_SLAB}
    assert set(fetched) == {
        2 * 4 * (rs_kernel._MIN_SLAB // rs_kernel.VERIFY_BLOCK) * 4}


# --- a dispatch's tail slab is padded where it lies (ISSUE 32) -----------------
#
# The jax dispatch layer places whole power-of-two slabs. A pass's buffers
# reach to the end of the tail slab of its widest dispatch, and a dispatch is
# handed over with that slack: the slab loop slices, for every slab, and
# whatever an earlier dispatch left in the slack pads the tail. What that can
# break: stale bytes there reaching a file or a count; a last piece that
# takes the slack with it; a buffer too narrow for its tail.

TAIL_SLABS = (4096, 4 * 4096)    # _MIN_SLAB and _MAX_SLAB, shrunk: a slab
#                                  still holds whole VERIFY_BLOCKs
TAIL_ROWS = 516                  # a shard of 132,096 B = 3 spans of 44,032:
TAIL_SPAN = 44_032               # two whole slabs and 11,264 lanes in a
TAIL_CHUNK = DATA_SHARDS * 45_000   # third of 16,384
TAIL_ROOM = 3 * TAIL_SLABS[1]


@pytest.fixture
def short_slabs(monkeypatch):
    from seaweedfs_tpu.ops import rs_kernel

    monkeypatch.setattr(rs_kernel, "_MIN_SLAB", TAIL_SLABS[0])
    monkeypatch.setattr(rs_kernel, "_MAX_SLAB", TAIL_SLABS[1])
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", REBUILD_FLOOR)
    assert rs_kernel.placed_lanes(TAIL_SPAN) == TAIL_ROOM
    assert fleet._stacked_spans(TAIL_CHUNK, [TAIL_ROWS * SMALL]) == \
        (TAIL_SPAN, 1)


def _padded():
    """Tail slabs by how they got their padding: (in_place, copied)."""
    from seaweedfs_tpu.stats.metrics import RsTailSlabsCounter
    return np.array([RsTailSlabsCounter.labels(p).value
                     for p in ("in_place", "copied")])


def _make_idle_buffers_stale(room):
    """Every byte of every idle buffer — the slack too — becomes 0xFF;
    each reaches as far as a dispatch of the pass before took."""
    assert fleet._IDLE_STAGING._bufs
    for buf in fleet._IDLE_STAGING._bufs:
        assert buf.shape[0] == TOTAL_SHARDS and buf.shape[1] >= room
        buf[:] = 0xFF


@pytest.mark.parametrize("lost", [(3,), (0, 3), (12,), (10, 11, 12, 13)])
def test_one_volume_rebuild_with_a_tail_slab_copies_nothing(tmp_path,
                                                            short_slabs, lost):
    """The scrub's repair: ONE volume, so every dispatch is one span —
    two whole slabs and a short third. The second rebuild runs in the
    first one's buffers with 0xFF in every lane: the rebuilt files are
    the numpy codec's, each tail slab is a slice of its buffer, and no
    buffer is made."""
    base, = _encoded(tmp_path, [TAIL_ROWS * ROW - 100], 130)
    want = {sid: open(shard_file_name(base, sid), "rb").read()
            for sid in lost}
    for stale in (False, True):
        for sid in lost:
            os.remove(shard_file_name(base, sid))
        before, fresh = _padded(), _handed("fresh")
        assert fleet.fleet_rebuild_ec_files(
            [base], backend="jax", chunk=TAIL_CHUNK) == {base: list(lost)}
        for sid in lost:
            with open(shard_file_name(base, sid), "rb") as f:
                assert f.read() == want[sid], f"shard {sid}, stale={stale}"
        assert list(_padded() - before) == [3, 0]
        if stale:
            assert _handed("fresh") == fresh
        else:
            _make_idle_buffers_stale(TAIL_ROOM)


VERIFY_TAIL_DAMAGE = {
    "clean": [],
    # in the tail slab of the last dispatch, and in its very last lane
    "parity-in-the-tail": [(12, 3 * TAIL_SPAN - 5000),
                           (12, TAIL_ROWS * SMALL - 1)],
    # a data byte: all four parity rows disagree there
    "data-in-the-tail": [(4, 2 * TAIL_SPAN + 2 * TAIL_SLABS[1] + 1)],
    "first-whole-slab": [(10, 0), (13, TAIL_SLABS[1] - 1)],
}


@pytest.mark.parametrize("case", sorted(VERIFY_TAIL_DAMAGE))
def test_one_volume_verify_with_a_tail_slab_copies_nothing(tmp_path,
                                                           short_slabs, case):
    """The scrub's re-verify of the repaired volume: every dispatch two
    whole slabs of all 14 rows and a short third. In buffers full of
    0xFF the verdict is a plain numpy compare's, byte count for byte
    count — the slack's blocks reach no count — and nothing is copied."""
    from seaweedfs_tpu.ops import rs_kernel

    base, = _encoded(tmp_path, [TAIL_ROWS * ROW - 100], 131)
    width = -(-TAIL_SPAN // rs_kernel.VERIFY_BLOCK) * rs_kernel.VERIFY_BLOCK
    assert width % TAIL_SLABS[1] and \
        rs_kernel.placed_lanes(width) == TAIL_ROOM
    assert fleet.fleet_verify_ec_files(
        [base], backend="jax", chunk=TAIL_CHUNK)[base].clean
    _make_idle_buffers_stale(TAIL_ROOM)
    for sid, offset in VERIFY_TAIL_DAMAGE[case]:
        _flip(base, sid, offset)
    want = _plain_verify(base)
    assert (case == "clean") == (not want["parity_mismatch"])
    before, fresh = _padded(), _handed("fresh")
    got = fleet.fleet_verify_ec_files([base], backend="jax",
                                      chunk=TAIL_CHUNK)[base]
    assert {f: getattr(got, f) for f in VERIFY_FIELDS} == want
    assert got.spans == 3 and got.clean == (case == "clean")
    assert list(_padded() - before) == [3, 0]
    assert _handed("fresh") == fresh


@pytest.mark.parametrize("rows, tails", [
    # chunk = 172 rows = 44,032 lanes. Two full dispatches, a last one of
    # 50 rows = 12,800 lanes: one short slab
    (2 * 172 + 50, 3),
    # a last dispatch of 64 rows = one whole slab of 16,384: two tails
    (2 * 172 + 64, 2),
    # one dispatch of 17 rows = 4,352 lanes in a slab of 8,192
    (17, 1),
])
def test_encode_whose_last_dispatch_has_a_tail_writes_whole_shards(
        tmp_path, short_slabs, rows, tails):
    """An encode's parity comes back as the lent rows' filled lanes: the
    last span's piece ends where the span ends, not where the slab does.
    Parity files are exactly a shard long and the serial encoder's, also
    out of buffers that held 0xFF."""
    chunk = 172 * ROW
    roomy = 1 << 20                  # small rows only, on the fleet's path
    for stale in (False, True):
        root = tmp_path / f"stale{stale}"
        root.mkdir()
        bases = _make_volumes(str(root), [rows * ROW - 9], seed=132)
        twins = _serial_twin(bases)
        ec.write_ec_files(twins[0], backend="numpy", large_block=roomy,
                          small_block=SMALL)
        before, fresh = _padded(), _handed("fresh")
        fleet.fleet_write_ec_files(bases, backend="jax", large_block=roomy,
                                   small_block=SMALL, chunk=chunk)
        _assert_shards_equal(bases, twins)
        assert {os.path.getsize(shard_file_name(bases[0], sid))
                for sid in range(TOTAL_SHARDS)} == {rows * SMALL}
        assert list(_padded() - before) == [tails, 0]
        if stale:
            assert _handed("fresh") == fresh
        else:
            _make_idle_buffers_stale(min(rows, 172) * SMALL)


def test_host_codecs_take_the_lanes_as_they_are(tmp_path, short_slabs):
    """No slab loop, no slack: a host pass's buffers are as wide as its
    dispatches (rounded up to a small block) and count no tail slab."""
    base, = _encoded(tmp_path, [TAIL_ROWS * ROW - 100], 133)
    os.remove(shard_file_name(base, 3))
    before = _padded()
    fleet.fleet_rebuild_ec_files([base], backend="numpy", chunk=TAIL_CHUNK)
    assert fleet.fleet_verify_ec_files(
        [base], backend="numpy", chunk=TAIL_CHUNK)[base].clean
    assert list(_padded() - before) == [0, 0]
    assert set(_idle_shapes()) == {(TOTAL_SHARDS, TAIL_SPAN)}


@pytest.mark.parametrize("lanes", [
    1, 4096, 4097, 16_384, 16_385, TAIL_SPAN, 3 * 16_384, 3 * 16_384 + 1,
    5 * 16_384 + 8192, 5 * 16_384 + 8193])
def test_a_pass_buffers_reach_the_end_of_its_tail_slab(short_slabs, lanes):
    """The capacity rule: what a jax pass takes of the idle list leaves
    room for the tail slab of its widest dispatch, whatever its lanes —
    and for the tail of every narrower dispatch, so a dispatcher never
    finds its buffer short. A host pass asks for its lanes alone."""
    from seaweedfs_tpu.ops import rs_kernel

    jax_d = fleet._Dispatcher(ReedSolomon(backend="jax"))
    host_d = fleet._Dispatcher(ReedSolomon(backend="numpy"))
    try:
        room = jax_d.room(lanes)
        assert room == rs_kernel.placed_lanes(lanes) >= lanes
        assert room - lanes < max(TAIL_SLABS[0], TAIL_SLABS[1] // 2)
        assert all(jax_d.room(n) <= room
                   for n in range(1, lanes, max(1, lanes // 97)))
        assert host_d.room(lanes) == lanes
    finally:
        host_d.close()
    st = fleet._Staging(room, 2, lambda: None)
    buf = st.acquire()
    assert buf.shape == (TOTAL_SHARDS, room)
    buf[:] = 1                        # filled: it goes to the idle list
    st.close()
    (_, capacity), = _idle_shapes()
    assert room <= capacity < room + REBUILD_FLOOR
    assert capacity % REBUILD_FLOOR == 0


def test_staging_capacity_only_grows(short_slabs):
    """Passes of any widths in any order: one capacity at a time, the
    widest room asked for so far rounded up to a small block; a narrower
    pass borrows what is idle, a wider one replaces it."""
    rng = np.random.default_rng(134)
    widest, kept = 0, None
    for lanes in rng.integers(1, 6 * TAIL_SLABS[1], 40):
        st = fleet._Staging(int(lanes), 1, lambda: None)
        buf = st.acquire()
        assert buf.shape == (TOTAL_SHARDS, lanes)
        buf[:] = 1
        st.close()
        grew = lanes > widest
        widest = max(widest, -(-int(lanes) // REBUILD_FLOOR) * REBUILD_FLOOR)
        (_, capacity), = _idle_shapes()
        assert capacity == widest
        if not grew:
            assert fleet._IDLE_STAGING._bufs[0] is kept
        kept = fleet._IDLE_STAGING._bufs[0]
