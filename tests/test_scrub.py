"""Scrub subsystem: scanner detection, planner classification and
repair, daemon pass/pause lifecycle, the fused fleet verify, and the
SEAWEED_VERIFY_READS read gate."""

import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder, fleet, store_ec
from seaweedfs_tpu.scrub import (EcDamage, ScrubDaemon, classify_ec_damage,
                                 repair_ec_volume, repair_needle,
                                 scan_ec_volume_needles, scan_volume)
from seaweedfs_tpu.storage import volume as volume_mod
from seaweedfs_tpu.storage.needle import (DataCorruptionError, Needle,
                                          masked_crc)
from seaweedfs_tpu.storage.store import Store

RNG = np.random.default_rng(42)


def _blob(n=2048):
    return RNG.integers(0, 256, n, dtype=np.uint8).tobytes()


def _flip_byte(path, offset, mask=0xFF):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ mask]))


def _corrupt_needle_data(v, nid):
    """Flip one byte inside needle nid's data region on disk; returns
    the flipped .dat offset."""
    nv = v.nm.get(nid)
    # header(16) + dataSize(4) puts us at the first data byte
    off = nv.offset + 16 + 4 + 3
    _flip_byte(v.dat_path, off)
    return off


@pytest.fixture
def store(tmp_path):
    s = Store([str(tmp_path)])
    yield s
    s.close()


def _fill_volume(store, vid, n=20, size=2048):
    store.add_volume(vid)
    v = store.find_volume(vid)
    for i in range(1, n + 1):
        v.write_needle(Needle(id=i, cookie=7, data=_blob(size)))
    return v


def _make_ec(store, vid, n=25, size=4096):
    v = _fill_volume(store, vid, n=n, size=size)
    base = store_ec.generate_ec_shards(store, vid, backend="numpy")
    store_ec.mount_ec_shards(store, vid, "", range(14))
    store.delete_volume(vid)
    return base


# -- scanner ------------------------------------------------------------------

class TestScanner:
    def test_clean_volume_scans_clean(self, store):
        v = _fill_volume(store, 1)
        res = scan_volume(v)
        assert res.needles_verified == 20
        assert res.bytes_scanned > 20 * 2048
        assert res.corrupt == []

    def test_detects_flipped_byte(self, store):
        v = _fill_volume(store, 1)
        _corrupt_needle_data(v, 5)
        res = scan_volume(v)
        assert [n.id for _, n in res.corrupt] == [5]

    def test_dead_copies_are_not_corruption(self, store):
        v = _fill_volume(store, 1, n=5)
        old = v.nm.get(3)
        v.write_needle(Needle(id=3, cookie=7, data=_blob()))  # overwrite
        # trash the OLD record's data: the live copy is elsewhere now
        _flip_byte(v.dat_path, old.offset + 16 + 4 + 1)
        res = scan_volume(v)
        assert res.corrupt == []

    def test_ec_needle_scan_localizes_bad_data_shard(self, store):
        base = _make_ec(store, 2)
        ecv = store.find_ec_volume(2)
        _, _, ivs = ecv.locate_needle(7)
        sid, soff = ivs[0].to_shard_and_offset(ecv.large_block,
                                               ecv.small_block)
        _flip_byte(encoder.shard_file_name(base, sid), soff + 30)
        res = scan_ec_volume_needles(ecv)
        assert 7 in res.corrupt
        assert res.bad_data_shards == {sid}

    def test_truncated_shard_does_not_abort_ec_scan(self, store):
        """A truncated data shard makes needle blobs SHORT — the parse
        dies in struct/index land, not as a clean NeedleError. The
        scanner must swallow it as corruption evidence, not abort the
        pass (regression)."""
        base = _make_ec(store, 2)
        ecv = store.find_ec_volume(2)
        with open(encoder.shard_file_name(base, 0), "r+b") as f:
            f.truncate(64)
        res = scan_ec_volume_needles(ecv)  # must not raise
        assert res.corrupt, "truncated-shard needles must read corrupt"

    def test_ec_needle_scan_clean(self, store):
        _make_ec(store, 2)
        res = scan_ec_volume_needles(store.find_ec_volume(2))
        assert res.corrupt == [] and res.needles_verified == 25


# -- fleet verify -------------------------------------------------------------

class TestFleetVerify:
    def test_parity_mismatch_located(self, tmp_path):
        bases = []
        for i in range(3):
            base = str(tmp_path / f"v{i}")
            with open(base + ".dat", "wb") as f:
                f.write(_blob((1 << 20) + i * 333))
            encoder.write_ec_files(base, backend="numpy")
            bases.append(base)
        res = fleet.fleet_verify_ec_files(bases, backend="numpy")
        assert all(r.clean and r.spans > 0 for r in res.values())
        _flip_byte(bases[1] + ".ec12", 777)
        res = fleet.fleet_verify_ec_files(bases, backend="numpy")
        assert res[bases[0]].clean and res[bases[2]].clean
        assert res[bases[1]].parity_mismatch == {12: 1}
        assert res[bases[1]].first_mismatch[12] == 777

    def test_data_corruption_contaminates_all_parity(self, tmp_path):
        base = str(tmp_path / "v")
        with open(base + ".dat", "wb") as f:
            f.write(_blob(1 << 20))
        encoder.write_ec_files(base, backend="numpy")
        _flip_byte(base + ".ec04", 1234)
        r = fleet.fleet_verify_ec_files([base], backend="numpy")[base]
        assert sorted(r.parity_mismatch) == [10, 11, 12, 13]

    def test_truncated_parity_shard_is_a_mismatch(self, tmp_path):
        """A parity file missing its tail must NOT verify clean: every
        absent byte counts as a mismatch (regression: the compare used
        to slice the recomputed parity down to whatever the file still
        had and pass)."""
        base = str(tmp_path / "v")
        with open(base + ".dat", "wb") as f:
            f.write(_blob(1 << 19))
        encoder.write_ec_files(base, backend="numpy")
        full = os.path.getsize(base + ".ec10")
        with open(base + ".ec10", "r+b") as f:
            f.truncate(full // 2)
        r = fleet.fleet_verify_ec_files([base], backend="numpy")[base]
        assert not r.clean
        assert r.parity_mismatch.get(10, 0) >= full - full // 2
        assert r.first_mismatch[10] == full // 2

    def test_missing_data_shard_not_verifiable(self, tmp_path):
        base = str(tmp_path / "v")
        with open(base + ".dat", "wb") as f:
            f.write(_blob(1 << 18))
        encoder.write_ec_files(base, backend="numpy")
        os.remove(base + ".ec03")
        r = fleet.fleet_verify_ec_files([base], backend="numpy")[base]
        assert not r.verified and r.missing == [3]


# -- planner ------------------------------------------------------------------

class TestPlanner:
    def test_classify(self):
        assert classify_ec_damage(EcDamage(base="b")) == ("clean", [])
        assert classify_ec_damage(EcDamage(
            base="b", parity_mismatch={11: 3})) == ("parity", [11])
        # data evidence wins over (contaminated) parity evidence
        assert classify_ec_damage(EcDamage(
            base="b", bad_data={2},
            parity_mismatch={10: 1, 11: 1, 12: 1, 13: 1})) == ("data", [2])
        assert classify_ec_damage(EcDamage(
            base="b", missing=[12])) == ("parity", [12])
        verdict, bad = classify_ec_damage(EcDamage(
            base="b", bad_data={0, 1, 2}, missing=[10, 11]))
        assert verdict == "unrecoverable" and len(bad) == 5

    def test_repair_quarantines_and_rebuilds_byte_identical(self, tmp_path):
        base = str(tmp_path / "v")
        with open(base + ".dat", "wb") as f:
            f.write(_blob(1 << 19))
        encoder.write_ec_files(base, backend="numpy")
        shard = base + ".ec02"
        with open(shard, "rb") as f:
            pristine = f.read()
        _flip_byte(shard, 99)
        rebuilt = repair_ec_volume(base, [2], backend="numpy")
        assert rebuilt == [2]
        assert os.path.exists(shard + ".corrupt")
        with open(shard, "rb") as f:
            assert f.read() == pristine
        assert fleet.fleet_verify_ec_files(
            [base], backend="numpy")[base].clean

    def test_repair_needle_from_replica(self, store):
        v = _fill_volume(store, 1)
        good = v.read_needle(Needle(id=9, cookie=7)).data
        _corrupt_needle_data(v, 9)
        with pytest.raises(DataCorruptionError):
            v.read_needle(Needle(id=9, cookie=7))
        corrupt = next(n for _, n in scan_volume(v).corrupt)

        # a replica serving WRONG bytes is rejected by the CRC pin
        assert not repair_needle(v, corrupt, lambda vid, n: b"wrong")
        # ... the right bytes land, even on a sealed volume
        v.read_only = True
        assert repair_needle(v, corrupt, lambda vid, n: good)
        assert v.read_only  # seal restored
        assert v.read_needle(Needle(id=9, cookie=7)).data == good

    def test_repair_needle_no_replica(self, store):
        v = _fill_volume(store, 1)
        _corrupt_needle_data(v, 3)
        corrupt = next(n for _, n in scan_volume(v).corrupt)
        assert not repair_needle(v, corrupt, lambda vid, n: None)


class TestSyndromeProbe:
    def test_names_the_corrupt_data_shard(self, tmp_path):
        from seaweedfs_tpu.scrub.planner import localize_from_parity_deltas
        base = str(tmp_path / "v")
        with open(base + ".dat", "wb") as f:
            f.write(_blob(1 << 19))
        encoder.write_ec_files(base, backend="numpy")
        # dead-space flip: way past the ~512KB of live data on shard 6
        _flip_byte(base + ".ec06", 900_000, mask=0x3C)
        r = fleet.fleet_verify_ec_files([base], backend="numpy")[base]
        assert sorted(r.parity_mismatch) == [10, 11, 12, 13]
        offsets = sorted(set(r.first_mismatch.values()))
        assert localize_from_parity_deltas(base, offsets) == {6}

    def test_parity_flip_is_not_misattributed(self, tmp_path):
        from seaweedfs_tpu.scrub.planner import localize_from_parity_deltas
        base = str(tmp_path / "v")
        with open(base + ".dat", "wb") as f:
            f.write(_blob(1 << 18))
        encoder.write_ec_files(base, backend="numpy")
        _flip_byte(base + ".ec11", 5000)
        r = fleet.fleet_verify_ec_files([base], backend="numpy")[base]
        assert localize_from_parity_deltas(
            base, sorted(set(r.first_mismatch.values()))) == set()


# -- daemon -------------------------------------------------------------------

class TestDaemon:
    def test_clean_pass(self, store):
        _fill_volume(store, 1)
        _make_ec(store, 2)
        d = ScrubDaemon(store, backend="numpy")
        res = d.run_pass()
        assert res.corruptions_found == 0
        assert res.needles_verified == 45  # 20 + 25
        assert res.stripes_verified > 0
        assert d.status()["passes_completed"] == 1

    def test_repairs_parity_and_data_shards(self, store):
        base = _make_ec(store, 2)
        ecv = store.find_ec_volume(2)
        # parity damage
        _flip_byte(base + ".ec13", 123)
        # data damage inside a live needle
        _, _, ivs = ecv.locate_needle(4)
        sid, soff = ivs[0].to_shard_and_offset(ecv.large_block,
                                               ecv.small_block)
        with open(encoder.shard_file_name(base, sid), "rb") as f:
            pristine = f.read()
        _flip_byte(encoder.shard_file_name(base, sid), soff + 40)
        d = ScrubDaemon(store, backend="numpy")
        res = d.run_pass()
        assert res.corruptions_found >= 2
        assert res.corruptions_repaired >= 2
        assert res.unrecoverable == 0
        with open(encoder.shard_file_name(base, sid), "rb") as f:
            assert f.read() == pristine, "reconstruction not byte-identical"
        assert os.path.exists(
            encoder.shard_file_name(base, sid) + ".corrupt")
        # next pass is clean, and reads still work through the ecv
        res2 = d.run_pass()
        assert res2.corruptions_found == 0
        got = ecv.read_needle(Needle(id=4, cookie=7))
        assert masked_crc(got.data) == got.checksum

    def test_dead_space_data_flip_repaired_byte_identical(self, store):
        """Corruption outside any live needle (zero padding) leaves no
        CRC evidence; the syndrome probe must still pin the data shard
        so it is rebuilt byte-identical instead of the parity being
        recomputed around the damage."""
        base = _make_ec(store, 2)
        shard = encoder.shard_file_name(base, 5)
        with open(shard, "rb") as f:
            pristine = f.read()
        _flip_byte(shard, len(pristine) - 100)  # deep in the padding
        d = ScrubDaemon(store, backend="numpy")
        res = d.run_pass()
        assert res.corruptions_repaired >= 1
        with open(shard, "rb") as f:
            assert f.read() == pristine
        assert os.path.exists(shard + ".corrupt")
        assert d.run_pass().corruptions_found == 0

    @pytest.mark.parametrize("sid, back", [
        (5, 100),         # a data shard, deep in the padding: the probe
        (0, 1 << 20),     # a data shard's first byte
        (12, 7777),       # a parity shard
    ])
    def test_jax_repair_and_reverify_pad_their_tail_slabs_in_place(
            self, store, monkeypatch, sid, back):
        """The repair's two ONE-volume passes, the rebuild of the
        condemned shard and the re-verify, on the jax backend with
        dispatches that are two whole slabs and a short third: the tail
        is a slice of the staging buffer like every other slab. With
        0xFF in every lane of every idle buffer the shard comes back
        byte-identical, the verdicts are the files', and the dispatch
        layer copied no tail."""
        from seaweedfs_tpu.ops import rs_kernel
        from seaweedfs_tpu.stats.metrics import RsTailSlabsCounter

        monkeypatch.setattr(rs_kernel, "_MIN_SLAB", 4096)
        monkeypatch.setattr(rs_kernel, "_MAX_SLAB", 4 * 4096)
        # 24 spans of 43,691 lanes a 1 MiB shard: 2 * 16,384 + 10,923
        monkeypatch.setattr(fleet, "default_chunk_for",
                            lambda backend: 10 * 45_000)
        monkeypatch.setattr(fleet, "_IDLE_STAGING", fleet._IdleStaging())
        base = _make_ec(store, 2)
        shard = encoder.shard_file_name(base, sid)
        with open(shard, "rb") as f:
            pristine = f.read()
        assert len(pristine) == 1 << 20
        d = ScrubDaemon(store, backend="jax")
        assert d.run_pass().corruptions_found == 0
        assert fleet._IDLE_STAGING._bufs
        for buf in fleet._IDLE_STAGING._bufs:
            assert buf.shape[1] >= rs_kernel.placed_lanes(43_691) == 49_152
            buf[:] = 0xFF
        _flip_byte(shard, len(pristine) - back)
        pads = {p: RsTailSlabsCounter.labels(p) for p in ("in_place",
                                                          "copied")}
        before = {p: c.value for p, c in pads.items()}
        res = d.run_pass()
        assert (res.corruptions_found, res.corruptions_repaired,
                res.unrecoverable) == (1, 1, 0)
        with open(shard, "rb") as f:
            assert f.read() == pristine
        assert os.path.exists(shard + ".corrupt")
        # the pass's verify, the rebuild and the re-verify: 24 each
        assert pads["in_place"].value - before["in_place"] == 3 * 24
        assert pads["copied"].value == before["copied"]
        assert d.run_pass().corruptions_found == 0

    def test_dead_space_probe_with_partial_local_parity(self, store):
        """Only 3 of 4 parity shards local: a dead-space data flip
        mismatches all THREE checked parity streams, and the probe must
        still name the data shard (regression: the all-four guard used
        to skip the probe, re-encode the local parity around the
        corrupt data, and report it repaired)."""
        base = _make_ec(store, 2)
        ecv = store.find_ec_volume(2)
        ecv.unmount_shard(13)
        os.remove(encoder.shard_file_name(base, 13))  # lives elsewhere
        shard = encoder.shard_file_name(base, 7)
        with open(shard, "rb") as f:
            pristine = f.read()
        _flip_byte(shard, len(pristine) - 200)  # dead space
        d = ScrubDaemon(store, backend="numpy")
        res = d.run_pass()
        assert res.corruptions_repaired >= 1
        with open(shard, "rb") as f:
            assert f.read() == pristine, \
                "data shard must be rebuilt byte-identical, not have " \
                "parity re-encoded around the damage"

    def test_needle_repair_via_replica_fetch(self, store):
        v = _fill_volume(store, 1)
        good = v.read_needle(Needle(id=2, cookie=7)).data
        _corrupt_needle_data(v, 2)
        d = ScrubDaemon(store, backend="numpy",
                        replica_fetch=lambda vid, n: good)
        res = d.run_pass()
        assert res.corruptions_found == 1
        assert res.corruptions_repaired == 1
        assert v.read_needle(Needle(id=2, cookie=7)).data == good

    def test_unrecoverable_without_replica(self, store):
        v = _fill_volume(store, 1)
        _corrupt_needle_data(v, 2)
        d = ScrubDaemon(store, backend="numpy")
        res = d.run_pass()
        assert res.corruptions_found == 1
        assert res.corruptions_repaired == 0
        assert res.unrecoverable == 1

    def test_store_level_targeted_scrub(self, store):
        base = _make_ec(store, 3)
        _flip_byte(base + ".ec12", 64)
        res = store_ec.scrub_ec_volume(store, 3, backend="numpy")
        assert res.corruptions_found >= 1
        assert res.corruptions_repaired >= 1
        assert fleet.fleet_verify_ec_files(
            [base], backend="numpy")[base].clean
        with pytest.raises(store_ec.EcShardNotFound):
            store_ec.scrub_ec_volume(store, 99, backend="numpy")

    def test_volume_ids_filter(self, store):
        _fill_volume(store, 1)
        v2 = _fill_volume(store, 2)
        _corrupt_needle_data(v2, 1)
        d = ScrubDaemon(store, backend="numpy")
        assert d.run_pass(volume_ids=[1]).corruptions_found == 0
        assert d.run_pass(volume_ids=[2]).corruptions_found == 1

    def test_start_pause_resume_lifecycle(self, store):
        _fill_volume(store, 1, n=5)
        d = ScrubDaemon(store, backend="numpy")
        assert d.status()["state"] == "idle"
        assert d.pause() is False          # nothing to pause
        assert d.start()
        for _ in range(100):
            if d.status()["passes_completed"]:
                break
            threading.Event().wait(0.05)
        assert d.status()["passes_completed"] >= 1
        d.stop()
        assert d.status()["state"] == "idle"

    def test_targeted_start_does_not_narrow_periodic_passes(self, store):
        """A one-off targeted/throttled start must scope only its own
        first pass: the interval loop reverts to the whole store and
        the server budget (regression: the override used to stick)."""
        v1 = _fill_volume(store, 1, n=3)
        _fill_volume(store, 2, n=3)
        _corrupt_needle_data(v1, 1)
        d = ScrubDaemon(store, backend="numpy", interval_s=0.05)
        assert d.start(volume_ids=[2], throttle_mbps=999.0)
        try:
            # pass 1 sees only clean volume 2; later whole-store passes
            # must find volume 1's corruption
            for _ in range(200):
                if d.totals.corruptions_found:
                    break
                threading.Event().wait(0.05)
            assert d.totals.corruptions_found >= 1
            assert d.mbps == 0.0  # one-off budget did not stick
        finally:
            d.stop()

    def test_scan_lag_gauge_moves_between_scrapes(self, store):
        """The exported scan lag is computed at COLLECTION time — a
        stalled scrubber's lag keeps rising on every scrape even if
        nobody calls status()."""
        import time as time_mod

        from seaweedfs_tpu.stats.metrics import REGISTRY

        def scrape() -> float:
            for line in REGISTRY.render().splitlines():
                if line.startswith("SeaweedFS_scrub_scan_lag_seconds "):
                    return float(line.rsplit(" ", 1)[1])
            raise AssertionError("gauge not exported")

        _fill_volume(store, 1, n=2)
        d = ScrubDaemon(store, backend="numpy")
        d.run_pass()
        first = scrape()
        time_mod.sleep(0.2)
        assert scrape() >= first + 0.15

    def test_construction_is_free(self, store):
        before = threading.active_count()
        ScrubDaemon(store, backend="numpy")
        assert threading.active_count() == before


# -- read gate ----------------------------------------------------------------

class TestVerifyReads:
    def test_corrupt_read_raises_typed_error(self, store):
        v = _fill_volume(store, 1, n=3)
        _corrupt_needle_data(v, 1)
        volume_mod.set_verify_reads(True)
        try:
            with pytest.raises(DataCorruptionError):
                v.read_needle(Needle(id=1, cookie=7))
        finally:
            volume_mod.set_verify_reads(False)
        # the parse-time CRC check raises the same typed error with the
        # gate off — corrupt never silently reads as bad bytes
        with pytest.raises(DataCorruptionError):
            v.read_needle(Needle(id=1, cookie=7))

    def test_gate_flag_roundtrip(self):
        assert not volume_mod.verify_reads_enabled()
        volume_mod.set_verify_reads(True)
        assert volume_mod.verify_reads_enabled()
        volume_mod.set_verify_reads(False)


# -- master scheduler planning ------------------------------------------------

def test_plan_scrub_stagger():
    from seaweedfs_tpu.server.master import plan_scrub_stagger
    assert plan_scrub_stagger([], 60) == []
    assert plan_scrub_stagger(["a"], 60) == [("a", 0.0)]
    plan = plan_scrub_stagger(["a", "b", "c"], 60)
    assert [u for u, _ in plan] == ["a", "b", "c"]
    assert [w for _, w in plan] == [0.0, 20.0, 20.0]
