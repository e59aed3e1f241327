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
from seaweedfs_tpu.scrub import scanner
from seaweedfs_tpu.stats.metrics import (ScrubNeedleSourceCounter,
                                         ScrubNeedlesCounter)
from seaweedfs_tpu.storage import volume as volume_mod
from seaweedfs_tpu.storage.needle import (DataCorruptionError, Needle,
                                          NeedleError, actual_size,
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


def _make_mixed_ec(store, vid):
    """An EC volume whose needles span blocks, shards and the row
    boundary at 10 MiB: needle 1 of one byte, 2-13 of 1 MiB + 1 (every
    one crosses a 1 MiB block, 10 and 11 lie across the end of the
    first small-block row), 14-17 of 4 KiB with a name."""
    store.add_volume(vid)
    v = store.find_volume(vid)
    v.write_needle(Needle(id=1, cookie=7, data=b"\x5a"))
    for i in range(2, 14):
        v.write_needle(Needle(id=i, cookie=7, data=_blob((1 << 20) + 1)))
    for i in range(14, 18):
        v.write_needle(Needle(id=i, cookie=7, data=_blob(4096),
                              name=b"n%d" % i))
    base = store_ec.generate_ec_shards(store, vid, backend="native")
    store_ec.mount_ec_shards(store, vid, "", range(14))
    store.delete_volume(vid)
    ecv = store.find_ec_volume(vid)
    rows = {iv.block_index // 10 for i in (10, 11)
            for iv in ecv.locate_needle(i)[2]}
    assert rows == {0, 1}, "no needle spans the row boundary"
    return base


def _placed(ecv, nid):
    return [iv.to_shard_and_offset(ecv.large_block, ecv.small_block)
            + (iv.size,) for iv in ecv.locate_needle(nid)[2]]


def _flip_in_record(ecv, base, nid, at):
    """Flip the byte `at` bytes into needle nid's stored record."""
    for sid, off, ln in _placed(ecv, nid):
        if at < ln:
            _flip_byte(encoder.shard_file_name(base, sid), off + at)
            return sid
        at -= ln
    raise AssertionError("offset past the record")


def _copied_scan(ecv, version=3):
    """The sweep as it was before the pool: one needle at a time through
    scanner.check_copied (read_at + join + Needle.from_bytes)."""
    res = scanner.EcNeedleScan()
    for key, size in zip(ecv._keys.tolist(), ecv._sizes.tolist()):
        if size < 0:
            continue
        try:
            placed = _placed(ecv, key)
        except NeedleError:
            continue
        got, bad = scanner.check_copied(ecv, placed, version, None)
        res.bytes_scanned += got
        res.needles_verified += 1
        if bad is not None:
            res.corrupt.append(key)
            res.bad_data_shards |= bad
    return res


def _needle_counts():
    return {c: ScrubNeedlesCounter.labels(c).value
            for c in ("in_place", "copied")}


def _source_counts():
    return {s: ScrubNeedleSourceCounter.labels(s).value
            for s in ("staged", "carried", "read")}


def _staged_scan(ecv, backend, chunk):
    """The EC part of a full pass, on one volume: the .ecx walked, the
    stripe verify with the needles checked in its staged bytes, then the
    sweep's rest."""
    staged = scanner.StagedSweep(ecv)
    verified = fleet.fleet_verify_ec_files(
        [ecv.base_name], backend=backend, chunk=chunk,
        on_span=lambda base, offset, valid, rows:
        staged.take(offset, valid, rows))
    assert verified[ecv.base_name].verified
    return scan_ec_volume_needles(ecv, staged=staged)


def _sources_by_geometry(ecv, span):
    """Where each live needle's bytes lie among spans of `span` shard
    bytes: all in one, across one end, or further apart."""
    found = {"staged": 0, "carried": 0, "read": 0}
    for key, size in zip(ecv._keys.tolist(), ecv._sizes.tolist()):
        if size < 0:
            continue
        placed = _placed(ecv, key)
        first = min(off for _, off, _ in placed) // span
        last = (max(off + ln for _, off, ln in placed) - 1) // span
        found[("staged", "carried")[last - first]
              if last - first < 2 else "read"] += 1
    return found


def _sector(path, offset):
    """Every byte of one 4096-byte sector replaced by another value."""
    with open(path, "r+b") as f:
        f.seek(offset)
        old = f.read(4096)
        assert len(old) == 4096
        f.seek(offset)
        f.write(bytes((b + 1 + i % 200) % 256 for i, b in enumerate(old)))


def _sector_in_payload(ecv, base):
    sid, off, ln = _placed(ecv, 7)[0]
    assert ln > 2 * 4096
    _sector(encoder.shard_file_name(base, sid), off + 100)
    return [7], {sid}


def _sector_in_dead_space(ecv, base):
    # row 1 holds needles on shards 0-2 only: shard 9 is padding there
    path = encoder.shard_file_name(base, 9)
    _sector(path, os.path.getsize(path) - 2 * 4096)
    return [], set()


def _tombstones(ecv, base):
    ecv.delete_needle(5)
    ecv.delete_needle(14)
    return [], set()


# what is planted -> (corrupt needles, bad data shards) of the sweep
_STAGED_CASES = {
    "clean": lambda ecv, base: ([], set()),
    "tombstoned": _tombstones,
    "sector-in-payload": _sector_in_payload,
    "sector-in-dead-space": _sector_in_dead_space,
    "sector-in-parity": lambda ecv, base: _sector(
        encoder.shard_file_name(base, 12), 3 * 4096) or ([], set()),
}


def _case_flip(nid, at_from_size):
    """Flip one byte of needle nid, `at_from_size(size)` into its
    record: one needle goes to the copied path."""
    def plant(ecv, base, monkeypatch):
        _flip_in_record(ecv, base, nid,
                        at_from_size(ecv.find_needle(nid)[1]))
        return 1
    return plant


def _case_truncated(ecv, base, monkeypatch):
    with open(encoder.shard_file_name(base, 0), "r+b") as f:
        f.truncate(64)
    return sum(1 for nid in ecv._keys.tolist()
               if any(sid == 0 and off + ln > 64
                      for sid, off, ln in _placed(ecv, nid)))


def _case_tombstoned(ecv, base, monkeypatch):
    ecv.delete_needle(5)
    ecv.delete_needle(14)
    return 0


def _case_over_the_cap(ecv, base, monkeypatch):
    monkeypatch.setattr(scanner, "_BUFFER_CAP", 1 << 20)
    _flip_in_record(ecv, base, 6, 16 + 4 + 999)
    return 12   # every needle of 1 MiB + 1, the damaged one among them


def _case_shard_missing(ecv, base, monkeypatch):
    ecv.unmount_shard(3)
    return 0


def _case_remote_shard(ecv, base, monkeypatch):
    """A tiered shard's intervals come through read_at: every needle
    with a byte on it is copied, and reads the same."""
    shard = ecv.shards[2]
    path = shard.path

    class Backend:
        def read_range(self, key, offset, length):
            with open(path, "rb") as f:
                f.seek(offset)
                return f.read(length)

    shard.swap_to_remote(Backend(), "k", shard.size)
    return sum(1 for nid in ecv._keys.tolist()
               if any(sid == 2 for sid, _, _ in _placed(ecv, nid)))


# what is planted -> how many needles must take the copied path
_SWEEP_CASES = {
    "clean": lambda ecv, base, monkeypatch: 0,
    "payload-byte": _case_flip(7, lambda size: 16 + 4 + 70000),
    "payload-byte-small-needle": _case_flip(15, lambda size: 16 + 4 + 9),
    "one-byte-needle": _case_flip(1, lambda size: 16 + 4),
    "header-id": _case_flip(3, lambda size: 4 + 7),
    "header-size": _case_flip(3, lambda size: 15),
    "data-size": _case_flip(8, lambda size: 16 + 2),
    "flags-after-payload": _case_flip(9, lambda size: 16 + 4 + (1 << 20) + 1),
    "name-size-after-payload": _case_flip(16, lambda size: 16 + 4 + 4096 + 1),
    "stored-checksum": _case_flip(12, lambda size: 16 + size + 1),
    "row-boundary-needle": _case_flip(11, lambda size: 16 + size - 50),
    "truncated-shard": _case_truncated,
    "tombstoned": _case_tombstoned,
    "over-the-cap": _case_over_the_cap,
    "shard-missing": _case_shard_missing,
    "remote-shard": _case_remote_shard,
}


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


    # -- the EC sweep: in place, several needles in flight ---------------------

    @pytest.mark.parametrize("case", sorted(_SWEEP_CASES))
    def test_ec_sweep_gives_what_the_copied_path_gives(self, store, case,
                                                       monkeypatch):
        """The sweep (in place, workers) and the per-needle path it
        falls back on (read_at + join + Needle.from_bytes), run over
        the same damaged volume, give the same EcNeedleScan; `copied`
        counts exactly the needles that were not clean in place."""
        base = _make_mixed_ec(store, 3)
        ecv = store.find_ec_volume(3)
        want_copied = _SWEEP_CASES[case](ecv, base, monkeypatch)
        live = sum(1 for z in ecv._sizes if z >= 0)
        counted = _needle_counts()
        got = scan_ec_volume_needles(ecv)
        moved = {c: n - counted[c] for c, n in _needle_counts().items()}
        want = _copied_scan(ecv)
        assert got == want
        assert got.needles_verified == live
        assert moved == {"in_place": got.needles_verified - want_copied,
                         "copied": want_copied}

    def test_ec_sweep_under_contention(self, store, monkeypatch):
        """More workers than cores and a switch interval of 10 us: the
        verdicts and their order are the copied path's."""
        import sys
        base = _make_mixed_ec(store, 3)
        ecv = store.find_ec_volume(3)
        for nid in (3, 9, 12):
            _flip_in_record(ecv, base, nid, 16 + 4 + 100)
        monkeypatch.setattr(scanner, "SWEEP_WORKERS", 16)
        monkeypatch.setattr(scanner, "_HANDOVER_BYTES", 0)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            got = scan_ec_volume_needles(ecv)
        finally:
            sys.setswitchinterval(old)
        assert got.corrupt == [3, 9, 12]
        assert got == _copied_scan(ecv)

    def test_ec_sweep_buffer_is_allocated_once_a_thread(self, store,
                                                        monkeypatch):
        _make_mixed_ec(store, 3)
        ecv = store.find_ec_volume(3)
        made = []
        new = scanner._new_buffer
        monkeypatch.setattr(scanner, "_new_buffer",
                            lambda n: made.append(n) or new(n))
        res = scan_ec_volume_needles(ecv)
        handed = sum(1 for z in ecv._sizes
                     if actual_size(int(z)) >= scanner._HANDOVER_BYTES)
        assert res.needles_verified == len(ecv._keys) and handed == 12
        # one a worker that got a needle, one for the sweeping thread's
        # small needles: not one a needle
        assert 2 <= len(made) <= scanner.SWEEP_WORKERS + 1
        assert sorted(set(made)) == [scanner._HANDOVER_BYTES,
                                     actual_size((1 << 20) + 1 + 5)]

    def test_ec_sweep_throttles_once_a_needle_with_its_length(self, store):
        _make_mixed_ec(store, 3)
        ecv = store.find_ec_volume(3)
        ecv.delete_needle(4)

        class Throttler:
            calls = []

            def maybe_slowdown(self, n):
                self.calls.append(n)

        res = scan_ec_volume_needles(ecv, throttler=Throttler())
        assert Throttler.calls == [actual_size(int(z)) for z in ecv._sizes
                                   if z >= 0]
        assert sum(Throttler.calls) == res.bytes_scanned

    # -- the staged sweep: needles checked in the stripe verify's bytes --------

    @pytest.mark.parametrize("backend, row", [
        ("numpy", 1 << 21),    # one span a shard: every needle whole in it
        ("numpy", 700_000),    # three: every 1 MiB needle across an end
        ("numpy", 1 << 16),    # 32: a 1 MiB needle over more than two
        ("jax", 700_000),      # counts from the device, spans of 4 KiB blocks
    ])
    @pytest.mark.parametrize("case", sorted(_STAGED_CASES))
    def test_staged_sweep_gives_what_the_disk_sweep_gives(
            self, store, case, backend, row):
        """The needles checked in the bytes the stripe verify staged (on
        the volume's writer lane, across a span's end from a copy) give
        the EcNeedleScan the disk sweep and the copied path give: a
        sector in a live needle's payload is found and named by its
        data shard, one in dead space or a parity shard by no needle.
        Each needle is counted once by where its bytes were found, as
        the verify's spans cut it."""
        base = _make_mixed_ec(store, 3)
        ecv = store.find_ec_volume(3)
        planted = _STAGED_CASES[case](ecv, base)
        counted = _needle_counts()
        sources = _source_counts()
        got = _staged_scan(ecv, backend, 10 * row)
        moved = {c: n - counted[c] for c, n in _needle_counts().items()}
        found = {s: n - sources[s] for s, n in _source_counts().items()}
        want = _copied_scan(ecv)
        assert got == want == scan_ec_volume_needles(ecv)
        assert (got.corrupt, got.bad_data_shards) == planted
        assert moved == {"in_place": got.needles_verified - len(planted[0]),
                         "copied": len(planted[0])}
        span, _ = fleet._stacked_spans(10 * row, [ecv.shard_size])
        assert found == _sources_by_geometry(ecv, span)
        assert found["carried"] > 0 or row != 700_000
        assert found["read"] > 0 or row != 1 << 16

    def test_a_volume_the_verify_declines_is_swept_from_disk(self, store):
        """A data shard gone: the stripe verify declines the volume, no
        span of it is staged, and its sweep reads every needle from
        disk — those with bytes on the lost shard reconstructed from the
        others; the other volume's are not read."""
        _make_ec(store, 2)
        base = _make_mixed_ec(store, 3)
        ecv = store.find_ec_volume(3)
        ecv.unmount_shard(3)
        os.remove(encoder.shard_file_name(base, 3))
        sources = _source_counts()
        res = ScrubDaemon(store, backend="numpy").run_pass()
        found = {s: n - sources[s] for s, n in _source_counts().items()}
        live = sum(1 for z in ecv._sizes if z >= 0)
        local = sum(1 for nid in ecv._keys.tolist()
                    if all(sid != 3 for sid, _, _ in _placed(ecv, nid)))
        assert 0 < local < live
        assert found == {"staged": 25, "carried": 0, "read": live}
        assert res.needles_verified == 25 + live
        assert res.corruptions_found == 0

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_a_staged_check_reads_no_buffer_handed_out_again(
            self, store, monkeypatch, backend):
        """Every staging buffer is overwritten the moment its last
        reader lets it go: the needles still come out clean where the
        verify staged them, so no check read a buffer after that (the
        pieces carried across a span's end are copies)."""
        monkeypatch.setattr(fleet, "_IDLE_STAGING", fleet._IdleStaging())
        unref = fleet._Staging.unref

        def poisoned(staging, batch):
            with staging._cond:
                if batch.refs == 1:
                    batch.buf[:] = 0xA5
            unref(staging, batch)

        monkeypatch.setattr(fleet._Staging, "unref", poisoned)
        _make_mixed_ec(store, 3)
        ecv = store.find_ec_volume(3)
        counted = _needle_counts()
        sources = _source_counts()
        got = _staged_scan(ecv, backend, 10 * 700_000)
        assert _needle_counts()["copied"] == counted["copied"]
        found = {s: n - sources[s] for s, n in _source_counts().items()}
        assert found["carried"] > 0 and found["read"] == 0
        assert got.needles_verified == found["staged"] + found["carried"]
        assert got == _copied_scan(ecv)

    def test_shard_read_into_equals_read_at(self, store):
        base = _make_ec(store, 2)
        ecv = store.find_ec_volume(2)
        shard = ecv.shards[0]
        for offset, length in ((0, 4096), (shard.size - 100, 4096),
                               (shard.size + 8, 16), (77, 1)):
            buf = bytearray(b"\xaa" * length)
            want = shard.read_at(offset, length)
            assert len(want) == max(0, min(length, shard.size - offset))
            assert shard.read_into(offset, memoryview(buf)) == len(want)
            assert buf[:len(want)] == want
            assert buf[len(want):] == b"\xaa" * (length - len(want))
        ecv.unmount_shard(0)   # closed under a reader that still holds it
        with pytest.raises(TypeError) as at:
            shard.read_at(0, 16)
        with pytest.raises(TypeError) as into:
            shard.read_into(0, memoryview(bytearray(16)))
        assert str(into.value) == str(at.value)


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
