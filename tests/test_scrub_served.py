"""The served scrub: shell `volume.scrub -full -wait` -> VolumeScrubStart
on every server -> VolumeScrubStatus(wait) -> the daemon's report.

Every case overwrites a 4096-byte sector of shard files UNDER the
mounted shards of EC volumes of a real in-process cluster whose codec
is jax (the stripe verify compares on the device and fetches counts),
runs the command, and holds it to what was planted: it names the volume
and the shard, all 14 files come back byte-equal to what they were —
which is the numpy encode of the data shards — the quarantined file is
kept, and the ledger counts what was planted and nothing else.
"""

import json
import os
import re

import numpy as np
import pytest

from seaweedfs_tpu.ec import fleet
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.ec.shard_bits import DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.operation.file_id import parse_fid
from seaweedfs_tpu.ops.rs_code import ReedSolomon
from seaweedfs_tpu.shell import CommandError, Shell
from seaweedfs_tpu.stats.metrics import (FleetStagingBuffersCounter,
                                         FleetVerifyBytesCounter,
                                         ScrubNeedleSourceCounter,
                                         ScrubNeedlesCounter,
                                         ScrubPhaseSecondsHistogram)
from seaweedfs_tpu.stats import trace
from tests.cluster_util import Cluster

COLLECTION = "sc"
SECTOR = 4096
PHASES = ("scan", "scan_ec", "verify", "repair", "reverify")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server, three EC volumes (jax codec) and a normal volume."""
    c = Cluster(tmp_path_factory.mktemp("scrub") / "cluster",
                n_volume_servers=1, ec_encoder="jax")
    try:
        with c.http(f"{c.master.url}/vol/grow?count=3"
                    f"&collection={COLLECTION}") as r:
            vids = sorted(json.load(r)["volumeIds"])
        filled = {}
        while set(filled) != set(vids) or min(filled.values()) < 4:
            fid = c.upload(os.urandom(20000), collection=COLLECTION)
            vid = parse_fid(fid).volume_id
            filled[vid] = filled.get(vid, 0) + 1
        c.upload(os.urandom(5000), collection="plain")
        shell = Shell(c.master.url)
        out = shell.run_command("ec.encode -volumeId=%s -encoder=jax"
                                % ",".join(map(str, vids)))
        for vid in vids:
            assert f"volume {vid}: ec.encode done" in out
            c.wait_for(lambda vid=vid: sum(
                b.count for b in c.master.topo.lookup_ec(vid).values())
                == TOTAL_SHARDS, what=f"14 shards of volume {vid}")
        yield c, shell, vids
    finally:
        c.stop()


def _files(c, vid):
    ecv = c.volume_servers[0].store.find_ec_volume(vid)
    assert sorted(ecv.shards) == list(range(TOTAL_SHARDS))
    return [shard_file_name(ecv.base_name, sid)
            for sid in range(TOTAL_SHARDS)]


def _read(paths):
    return [np.fromfile(p, dtype=np.uint8) for p in paths]


def _plant(path, offset):
    """Every byte of one sector replaced by another value."""
    with open(path, "r+b") as f:
        f.seek(offset)
        old = f.read(SECTOR)
        assert len(old) == SECTOR
        f.seek(offset)
        f.write(bytes((b + 1 + i % 200) % 256 for i, b in enumerate(old)))
    return old


def _ledger(out):
    m = re.search(r": (\w+) passes:(\d+) scanned:\d+B needles:\d+ "
                  r"stripes:\d+ found:(\d+) repaired:(\d+) "
                  r"unrecoverable:(\d+)", out)
    assert m, out
    return (m.group(1),) + tuple(int(x) for x in m.groups()[1:])


def _verdicts(out):
    return {int(vid): verdict for vid, verdict in
            re.findall(r": volume (\d+): (.+)", out)}


def _phase_counts():
    return {p: ScrubPhaseSecondsHistogram.labels(p).count for p in PHASES}


def _needle_counts():
    return {c: ScrubNeedlesCounter.labels(c).value
            for c in ("in_place", "copied")}


def _source_counts():
    return {s: ScrubNeedleSourceCounter.labels(s).value
            for s in ("staged", "carried", "read")}


@pytest.mark.parametrize("case", ["none", "data-shard", "parity-shard",
                                  "dead-space", "two-volumes"])
def test_scrub_wait_reports_what_was_planted_and_repairs_it(served, case):
    c, shell, vids = served
    # shard 0 holds the needles of these small volumes, shards 1-9 the
    # zero padding of their one row: damage no live needle's CRC covers
    planted = {
        "none": {},
        "data-shard": {vids[1]: (0, SECTOR)},
        "parity-shard": {vids[0]: (12, 3 * SECTOR)},
        "dead-space": {vids[2]: (3, 5 * SECTOR)},
        "two-volumes": {vids[0]: (0, 2 * SECTOR), vids[2]: (11, 0)},
    }[case]
    paths = {vid: _files(c, vid) for vid in vids}
    before = {vid: _read(paths[vid]) for vid in vids}
    rs = ReedSolomon(backend="numpy")
    for vid in vids:   # what the jax encode left is the numpy encode
        assert np.array_equal(rs.encode(np.stack(before[vid][:DATA_SHARDS])),
                              np.stack(before[vid][DATA_SHARDS:]))
    for vid, (sid, offset) in planted.items():
        _plant(paths[vid][sid], offset)
    assert not trace.active()
    phases = _phase_counts()
    needles = _needle_counts()
    sources = _source_counts()
    device = FleetVerifyBytesCounter.labels("device").value
    host = FleetVerifyBytesCounter.labels("host").value

    out = shell.run_command("volume.scrub -full -wait")

    assert out.count("scrub started") == 1
    assert _ledger(out) == ("idle", 1, len(planted), len(planted), 0)
    assert _verdicts(out) == {
        vid: f"rebuilt shards [{planted[vid][0]}]" if vid in planted
        else "clean" for vid in vids + _plain_vids(c)}
    for vid in vids:
        after = _read(_files(c, vid))          # all 14 mounted again
        for sid in range(TOTAL_SHARDS):
            assert np.array_equal(after[sid], before[vid][sid]), (vid, sid)
    for vid, (sid, offset) in planted.items():
        kept = np.fromfile(paths[vid][sid] + ".corrupt", dtype=np.uint8)
        differ = np.flatnonzero(kept != before[vid][sid])
        assert len(differ) == SECTOR and differ[0] == offset
        os.remove(paths[vid][sid] + ".corrupt")
    # every phase of the pass is observed with the span ring off: an EC
    # volume's sweep twice, its .ecx walk before the verify and its rest
    moved = {p: n - phases[p] for p, n in _phase_counts().items()}
    assert moved == {"scan": len(_plain_vids(c)), "scan_ec": 2 * len(vids),
                     "verify": 1,
                     "repair": len(planted), "reverify": len(planted)}
    # the EC sweep declared every needle clean where it was read, but
    # the one under a sector planted in a data shard's live bytes
    # (shard 0 here), which the copied path called corrupt
    swept = {c: n - needles[c] for c, n in _needle_counts().items()}
    live = sum(c.volume_servers[0].store.find_ec_volume(vid).file_count()
               for vid in vids)
    assert swept == {
        "copied": sum(1 for sid, _ in planted.values() if sid == 0),
        "in_place": live - swept["copied"]} and swept["copied"] <= 1
    # ... and every one of them in the bytes the stripe verify staged:
    # one server holds every shard, so nothing was read a second time
    found = {s: n - sources[s] for s, n in _source_counts().items()}
    assert found["read"] == 0
    assert found["staged"] + found["carried"] == live
    # the stripe verify and the re-verifies compared on the device
    assert FleetVerifyBytesCounter.labels("device").value - device == \
        DATA_SHARDS * sum(len(before[vid][0])
                          for vid in vids + list(planted))
    assert FleetVerifyBytesCounter.labels("host").value == host


def _plain_vids(c):
    """The normal volumes (an upload to a new collection grows several)."""
    return sorted(vid for loc in c.volume_servers[0].store.locations
                  for vid in loc.volumes)


def test_two_passes_back_to_back_reuse_the_staging_buffers(served):
    c, shell, vids = served
    shell.run_command("volume.scrub -full -wait")
    fresh = FleetStagingBuffersCounter.labels("fresh").value
    reused = FleetStagingBuffersCounter.labels("reused").value
    out = shell.run_command("volume.scrub -full -wait")
    assert _ledger(out)[:2] == ("idle", 1)
    assert FleetStagingBuffersCounter.labels("fresh").value == fresh
    assert FleetStagingBuffersCounter.labels("reused").value > reused


def test_without_wait_the_command_says_what_it_said(served):
    c, shell, vids = served
    vs = c.volume_servers[0]
    ended = vs.scrub.passes_ended
    out = shell.run_command("volume.scrub -full")
    assert out == f"{vs.url}: scrub started\n"
    # the pass it started can still be waited for, by anyone
    assert vs.scrub.wait_pass(ended, timeout=120)
    status = shell.run_command("volume.scrub -status")
    assert _ledger(status)[:2] == ("idle", 1) and "volume" not in status


def test_a_pass_that_raises_reads_as_failed_not_as_idle(served, monkeypatch):
    c, shell, vids = served

    def broken(*a, **kw):
        raise RuntimeError("the stripe verify broke")

    monkeypatch.setattr(fleet, "fleet_verify_ec_files", broken)
    with pytest.raises(CommandError, match="scrub pass failed: "
                       "RuntimeError: the stripe verify broke") as e:
        shell.run_command("volume.scrub -full -wait")
    assert _ledger(e.value.partial)[:2] == ("failed", 0)
    status = shell.run_command("volume.scrub -status")
    assert _ledger(status)[:2] == ("failed", 0)
    monkeypatch.undo()
    out = shell.run_command("volume.scrub -full -wait")
    assert _ledger(out) == ("idle", 1, 0, 0, 0)
