"""The served `ec.rebuild`: shell -> ONE VolumeEcShardsRebuild a
rebuilder -> store_ec.rebuild_ec_shards_batch -> ec/fleet.py.

Every case loses shards of EC volumes of a real in-process cluster the
way an operator's tooling drops them (unmount + delete through gRPC),
runs the shell command, and holds all 14 shard files of every volume
against (a) the files as they were before the loss and (b) the serial
reference path, `encoder.rebuild_ec_files(backend="numpy")`, run on a
copy of the survivors.
"""

import json
import os
import shutil

import pytest

from seaweedfs_tpu.ec import encoder, fleet
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.ec.shard_bits import TOTAL_SHARDS
from seaweedfs_tpu.operation.file_id import parse_fid
from seaweedfs_tpu.pb import volume_server_pb2 as pb
from seaweedfs_tpu.pb import volume_stub
from seaweedfs_tpu.resilience import failpoint
from seaweedfs_tpu.shell import CommandError, Shell
from tests.cluster_util import Cluster

COLLECTION = "rb"


@pytest.fixture()
def one_server(tmp_path):
    c = Cluster(tmp_path / "cluster", n_volume_servers=1)
    yield c
    c.stop()


def _encode_volumes(c, n, backend, collection=COLLECTION):
    """`n` EC volumes of `collection`, each with a few needles, all 14
    shards registered; returns their ids."""
    with c.http(f"{c.master.url}/vol/grow?count={n}"
                f"&collection={collection}") as r:
        vids = sorted(json.load(r)["volumeIds"])
    assert len(vids) == n
    filled = set()
    while filled != set(vids):
        fid = c.upload(os.urandom(3000), collection=collection)
        filled.add(parse_fid(fid).volume_id)
    out = Shell(c.master.url).run_command(
        "ec.encode -volumeId=%s -encoder=%s"
        % (",".join(map(str, vids)), backend))
    for vid in vids:
        assert f"volume {vid}: ec.encode done" in out
    _wait_registered(c, {vid: TOTAL_SHARDS for vid in vids})
    return vids


def _registered(c, vid):
    return sum(b.count for b in c.master.topo.lookup_ec(vid).values())


def _wait_registered(c, want):
    for vid, n in want.items():
        c.wait_for(lambda vid=vid, n=n: _registered(c, vid) == n,
                   what=f"{n} shards of volume {vid} at the master")


def _mounted_files(c, vid):
    """shard id -> path of its file on the server that serves it."""
    files = {}
    for vs in c.volume_servers:
        ecv = vs.store.find_ec_volume(vid)
        for sid in (ecv.shards if ecv is not None else ()):
            assert sid not in files, f"shard {sid} of {vid} mounted twice"
            files[sid] = shard_file_name(ecv.base_name, sid)
    return files


def _read_all(files):
    out = {}
    for sid, path in files.items():
        with open(path, "rb") as f:
            out[sid] = f.read()
    return out


def _lose(c, vid, lost, collection=COLLECTION):
    """Unmount + delete `lost` wherever they are served."""
    for vs in c.volume_servers:
        ecv = vs.store.find_ec_volume(vid)
        here = [s for s in lost if ecv is not None and s in ecv.shards]
        if not here:
            continue
        stub = volume_stub(vs.url)
        stub.VolumeEcShardsUnmount(pb.VolumeEcShardsUnmountRequest(
            volume_id=vid, shard_ids=here))
        stub.VolumeEcShardsDelete(pb.VolumeEcShardsDeleteRequest(
            volume_id=vid, collection=collection, shard_ids=here))


def _serial_reference(tmp_path, vid, survivors):
    """`encoder.rebuild_ec_files(backend="numpy")` over a copy of the
    surviving files: shard id -> bytes of all 14."""
    d = tmp_path / f"serial-{vid}"
    d.mkdir(parents=True)
    base = str(d / str(vid))
    for sid, path in survivors.items():
        shutil.copyfile(path, shard_file_name(base, sid))
    rebuilt = encoder.rebuild_ec_files(base, backend="numpy")
    assert sorted(rebuilt) == sorted(set(range(TOTAL_SHARDS))
                                     - set(survivors))
    return _read_all({sid: shard_file_name(base, sid)
                      for sid in range(TOTAL_SHARDS)})


def _lose_rebuild_compare(c, tmp_path, losses, backend, command=None,
                          unrepairable=()):
    """Lose `losses` = {vid: shard ids}, run the shell command, compare
    every volume not in `unrepairable`. Returns the command's output."""
    before = {vid: _read_all(_mounted_files(c, vid)) for vid in losses}
    for vid in before:
        assert sorted(before[vid]) == list(range(TOTAL_SHARDS))
    for vid, lost in losses.items():
        _lose(c, vid, lost)
    _wait_registered(c, {vid: TOTAL_SHARDS - len(lost)
                         for vid, lost in losses.items()})
    serial = {vid: _serial_reference(tmp_path, vid, _mounted_files(c, vid))
              for vid in losses if vid not in unrepairable}
    out = Shell(c.master.url).run_command(
        command or f"ec.rebuild -encoder={backend}")
    for vid, lost in losses.items():
        if vid in unrepairable:
            continue
        assert f"volume {vid}: rebuilt shards {sorted(lost)} on " in out
        _wait_registered(c, {vid: TOTAL_SHARDS})
        after = _read_all(_mounted_files(c, vid))
        assert sorted(after) == list(range(TOTAL_SHARDS))
        for sid in range(TOTAL_SHARDS):
            assert after[sid] == before[vid][sid], \
                f"volume {vid} shard {sid}: not the bytes before the loss"
            assert after[sid] == serial[vid][sid], \
                f"volume {vid} shard {sid}: not the serial path's bytes"
    return out


def _sample(c, series):
    """One series of the cluster's /metrics (0.0 while it has none)."""
    with c.http(f"{c.metrics_url}/metrics") as r:
        for line in r.read().decode().splitlines():
            if line.startswith(series + " "):
                return float(line.rsplit(" ", 1)[1])
    return 0.0


REBUILD_CALLS = ('SeaweedFS_request_total{type="volumeServer",'
                 'name="VolumeEcShardsRebuild"}')
GROUPS = "SeaweedFS_fleet_rebuild_groups_total"
VOLUMES = "SeaweedFS_fleet_rebuild_volumes_total"
REBUILT_BYTES = "SeaweedFS_fleet_rebuilt_bytes_total"

# what each volume of the case loses; volumes with the same loss share a
# (present, missing) signature, and so a decode matrix and its dispatches
SIGNATURES = {
    "one_data_shard": [[4]],
    "data_0_and_3": [[0, 3], [0, 3]],
    "one_data_one_parity": [[2, 12]],
    "four_lost": [[0, 3, 10, 13]],
    "two_share_a_signature_one_does_not": [[0, 3], [5, 11], [0, 3]],
}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("case", sorted(SIGNATURES))
def test_served_rebuild_restores_every_shard(one_server, tmp_path, case,
                                             backend):
    c, lost = one_server, SIGNATURES[case]
    vids = _encode_volumes(c, len(lost), backend)
    calls, groups, volumes, nbytes = (
        _sample(c, s) for s in (REBUILD_CALLS, GROUPS, VOLUMES,
                                REBUILT_BYTES))
    _lose_rebuild_compare(c, tmp_path, dict(zip(vids, lost)), backend)
    # the batching itself: one RPC for the rebuilder whatever the number
    # of volumes, one group a signature, every volume in the one pass
    assert _sample(c, REBUILD_CALLS) - calls == 1
    assert _sample(c, GROUPS) - groups == len({tuple(x) for x in lost})
    assert _sample(c, VOLUMES) - volumes == len(lost)
    shard_size = os.path.getsize(_mounted_files(c, vids[0])[0])
    assert _sample(c, REBUILT_BYTES) - nbytes == \
        shard_size * sum(len(x) for x in lost)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_shard_size_that_is_no_multiple_of_the_span(one_server, tmp_path,
                                                      monkeypatch, backend):
    """The last span of a shard is zero-padded to the span's width in
    the staging buffer and trimmed on the way to the file."""
    for name in ("DEFAULT_CHUNK", "DEFAULT_CHUNK_JAX"):
        monkeypatch.setattr(encoder, name, 1_000_003)
    # spans this narrow stack only under a floor to match
    monkeypatch.setattr(fleet, "SMALL_BLOCK_SIZE", 4096)
    c = one_server
    vids = _encode_volumes(c, 2, backend)
    shard_size = os.path.getsize(_mounted_files(c, vids[0])[0])
    span, per_batch = fleet._stacked_spans(1_000_003, [shard_size] * 2)
    assert per_batch == 2 and shard_size > span and shard_size % span
    _lose_rebuild_compare(c, tmp_path, {v: [0, 3] for v in vids}, backend)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_volume_past_repair_does_not_stop_the_others(one_server, tmp_path,
                                                       backend):
    c = one_server
    hopeless, a, b = _encode_volumes(c, 3, backend)
    out = _lose_rebuild_compare(
        c, tmp_path, {hopeless: [0, 1, 2, 3, 4], a: [0, 3], b: [0, 3]},
        backend, unrepairable=[hopeless])
    assert f"volume {hopeless}: only 9 shards left, cannot rebuild" in out
    assert _registered(c, hopeless) == 9
    assert not os.path.exists(shard_file_name(
        c.volume_servers[0].store.find_ec_volume(hopeless).base_name, 0))


def test_collection_flag_limits_the_rebuild(one_server, tmp_path):
    c = one_server
    (mine,) = _encode_volumes(c, 1, "numpy", collection="mine")
    (other,) = _encode_volumes(c, 1, "numpy", collection="other")
    _lose(c, other, [1], collection="other")
    _wait_registered(c, {other: 13})
    out = _lose_rebuild_compare(
        c, tmp_path, {mine: [0, 3]}, "numpy",
        command="ec.rebuild -collection=mine -encoder=numpy")
    assert f"volume {other}" not in out
    assert _registered(c, other) == 13
    out = Shell(c.master.url).run_command(
        "ec.rebuild -collection=other -encoder=numpy")
    assert f"volume {other}: rebuilt shards [1] on " in out


def test_the_one_volume_id_request_behaves_as_before(one_server, tmp_path):
    """An old client's request, `volume_id` alone: the answer has the
    shape it had (`rebuilt_shard_ids`) and the files their bytes."""
    c = one_server
    (vid,) = _encode_volumes(c, 1, "numpy")
    before = _read_all(_mounted_files(c, vid))
    _lose(c, vid, [0, 3])
    stub = volume_stub(c.volume_servers[0].url)
    resp = stub.VolumeEcShardsRebuild(pb.VolumeEcShardsRebuildRequest(
        volume_id=vid, collection=COLLECTION, encoder="numpy"))
    assert list(resp.rebuilt_shard_ids) == [0, 3]
    assert [(r.volume_id, list(r.rebuilt_shard_ids))
            for r in resp.results] == [(vid, [0, 3])]
    stub.VolumeEcShardsMount(pb.VolumeEcShardsMountRequest(
        volume_id=vid, collection=COLLECTION, shard_ids=[0, 3]))
    assert _read_all(_mounted_files(c, vid)) == before


def test_a_volume_ids_request_answers_per_volume(one_server):
    c = one_server
    a, b = _encode_volumes(c, 2, "numpy")
    _lose(c, a, [0, 3])
    _lose(c, b, [7])
    stub = volume_stub(c.volume_servers[0].url)
    resp = stub.VolumeEcShardsRebuild(pb.VolumeEcShardsRebuildRequest(
        volume_id=b, volume_ids=[b, a, b], collection=COLLECTION,
        encoder="numpy"))
    assert [(r.volume_id, list(r.rebuilt_shard_ids))
            for r in resp.results] == [(b, [7]), (a, [0, 3])]
    assert list(resp.rebuilt_shard_ids) == [7]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_two_servers_pull_survivors_to_the_rebuilder(tmp_path, backend):
    """ec.encode spreads the shards over both servers; the rebuilder
    holds fewer than 10 of each volume and pulls the rest, rebuilds
    both volumes in one RPC, and drops the pulled copies."""
    c = Cluster(tmp_path / "cluster", n_volume_servers=2)
    try:
        vids = _encode_volumes(c, 2, backend)
        for vid in vids:
            assert len(c.master.topo.lookup_ec(vid)) == 2
        calls = _sample(c, REBUILD_CALLS)
        _lose_rebuild_compare(c, tmp_path, {v: [0, 3] for v in vids},
                              backend)
        assert _sample(c, REBUILD_CALLS) - calls == 1
        # no scaffolding left: every shard file on a disk is a mounted one
        for vid in vids:
            mounted = set(_mounted_files(c, vid).values())
            for vs in c.volume_servers:
                ecv = vs.store.find_ec_volume(vid)
                on_disk = {shard_file_name(ecv.base_name, sid)
                           for sid in range(TOTAL_SHARDS)
                           if os.path.exists(
                               shard_file_name(ecv.base_name, sid))}
                assert on_disk <= mounted
    finally:
        c.stop()


def test_a_volume_that_fails_to_mount_keeps_no_pulled_copy(tmp_path,
                                                           monkeypatch):
    """One volume's mount fails after the rebuild: it is reported, the
    other volume is repaired, and the survivors pulled for the failed
    one do not stay on the rebuilder. The next command repairs it."""
    from seaweedfs_tpu.ec import store_ec
    from seaweedfs_tpu.ec.ec_volume import EcShardNotFound
    c = Cluster(tmp_path / "cluster", n_volume_servers=2)
    try:
        bad, good = _encode_volumes(c, 2, "numpy")
        before = {v: _read_all(_mounted_files(c, v)) for v in (bad, good)}
        for vid in (bad, good):
            _lose(c, vid, [0, 3])
        _wait_registered(c, {bad: 12, good: 12})
        survivors = set(_mounted_files(c, bad).values())
        mount = store_ec.mount_ec_shards

        def mount_but_one(store, vid, collection, shard_ids):
            if vid == bad:
                raise EcShardNotFound(f"volume {vid}: mount refused")
            return mount(store, vid, collection, shard_ids)

        monkeypatch.setattr(store_ec, "mount_ec_shards", mount_but_one)
        shell = Shell(c.master.url)
        with pytest.raises(CommandError) as e:
            shell.run_command("ec.rebuild -encoder=numpy")
        monkeypatch.undo()
        assert f"volume {bad}: ec.rebuild failed" in str(e.value)
        assert f"volume {good}: rebuilt shards [0, 3] on " in e.value.partial
        _wait_registered(c, {good: TOTAL_SHARDS})
        assert _read_all(_mounted_files(c, good)) == before[good]
        # on the disks: the survivors where they are served, and the two
        # rebuilt files that wait for their mount
        on_disk = set()
        for vs in c.volume_servers:
            base = vs.store.find_ec_volume(bad).base_name
            on_disk |= {shard_file_name(base, sid)
                        for sid in range(TOTAL_SHARDS)
                        if os.path.exists(shard_file_name(base, sid))}
        assert len(on_disk - survivors) == 2 and survivors <= on_disk
        out = shell.run_command("ec.rebuild -encoder=numpy")
        assert f"volume {bad}: rebuilt shards [0, 3] on " in out
        _wait_registered(c, {bad: TOTAL_SHARDS})
        assert _read_all(_mounted_files(c, bad)) == before[bad]
    finally:
        c.stop()


def test_a_pass_that_fails_half_way_mounts_nothing(one_server, monkeypatch):
    """The fleet pass creates the missing shard files before it fills
    them. A pass that fails at its second dispatch fails the RPC and the
    command, leaves no partial file on the disk, mounts nothing and
    registers nothing; the next command repairs the volumes."""
    c = one_server
    vids = _encode_volumes(c, 2, "numpy")
    before = {vid: _read_all(_mounted_files(c, vid)) for vid in vids}
    for vid in vids:
        _lose(c, vid, [0, 3])
    _wait_registered(c, {vid: 12 for vid in vids})
    shell = Shell(c.master.url)
    # many dispatches a pass; the failpoint is armed by the first
    for name in ("DEFAULT_CHUNK", "DEFAULT_CHUNK_JAX"):
        monkeypatch.setattr(encoder, name, 1_000_003)
    reconstruct = fleet._Dispatcher.reconstruct_lanes

    def reconstruct_then_arm(self, *args):
        handle = reconstruct(self, *args)
        failpoint.arm("fleet.dispatch", "error",
                      match={"op": "reconstruct"})
        return handle

    monkeypatch.setattr(fleet._Dispatcher, "reconstruct_lanes",
                        reconstruct_then_arm)
    try:
        with pytest.raises(CommandError) as e:
            shell.run_command("ec.rebuild -encoder=numpy")
    finally:
        failpoint.disarm("fleet.dispatch")
        monkeypatch.undo()
    assert "rebuild failed" in str(e.value)
    for vid in vids:
        assert f"volume {vid}: rebuilt shards" not in e.value.partial
        ecv = c.volume_servers[0].store.find_ec_volume(vid)
        assert sorted(ecv.shards) == [s for s in range(TOTAL_SHARDS)
                                      if s not in (0, 3)]
        for sid in (0, 3):
            assert not os.path.exists(shard_file_name(ecv.base_name, sid))
        assert _registered(c, vid) == 12
    out = shell.run_command("ec.rebuild -encoder=numpy")
    for vid in vids:
        assert f"volume {vid}: rebuilt shards [0, 3] on " in out
        _wait_registered(c, {vid: TOTAL_SHARDS})
        assert _read_all(_mounted_files(c, vid)) == before[vid]


STAGING = 'SeaweedFS_fleet_staging_buffers_total{state="%s"}'


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_second_rebuild_command_runs_in_the_first_one_s_buffers(
        one_server, tmp_path, monkeypatch, backend):
    """Each round of repair is one shell command, so one fleet pass. The
    pass reads the survivors into staging buffers that the server keeps:
    the second command is handed only buffers the first one filled."""
    monkeypatch.setattr(fleet, "_IDLE_STAGING", fleet._IdleStaging())
    c = one_server
    vids = _encode_volumes(c, 2, backend)
    losses = {v: [0, 3] for v in vids}
    fresh, reused = (_sample(c, STAGING % s) for s in ("fresh", "reused"))
    _lose_rebuild_compare(c, tmp_path / "first", losses, backend)
    fresh1, reused1 = (_sample(c, STAGING % s) for s in ("fresh", "reused"))
    assert (fresh1 - fresh) + (reused1 - reused) >= 1
    _lose_rebuild_compare(c, tmp_path / "second", losses, backend)
    assert _sample(c, STAGING % "reused") - reused1 == \
        (fresh1 - fresh) + (reused1 - reused)
    assert _sample(c, STAGING % "fresh") == fresh1


def test_the_pass_is_traced_stage_by_stage(one_server, tmp_path):
    """The spans the per-layer metrics read: the shell's two, the
    store's, one `fleet.rebuild` a signature with the signature in its
    tags, and every stage of the pass under the encode pass's timers."""
    from seaweedfs_tpu.stats import trace
    c = one_server
    vids = _encode_volumes(c, 3, "jax")
    stages = ("read", "pack", "dispatch", "retire", "write")
    counts = {s: _sample(c, 'SeaweedFS_fleet_stage_seconds_count'
                            '{stage="%s"}' % s) for s in stages}
    trace.enable()
    trace.clear()
    try:
        _lose_rebuild_compare(
            c, tmp_path, dict(zip(vids, [[0, 3], [0, 3], [6]])), "jax")
        spans = trace.spans()
    finally:
        trace.disable()
        trace.clear()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    url = c.volume_servers[0].url
    for name in ("shell.ec_rebuild.pull", "shell.ec_rebuild.rebuild"):
        (s,) = by_name[name]
        assert s.tags == {"rebuilder": url, "volumes": 3}
    (s,) = by_name["store_ec.rebuild_batch"]
    assert s.tags == {"volumes": 3}
    survivors = [i for i in range(TOTAL_SHARDS) if i not in (0, 3)]
    assert sorted((s.tags["volumes"], s.tags["groups"], s.tags["present"],
                   s.tags["missing"]) for s in by_name["fleet.rebuild"]) == \
        sorted([(2, 2, survivors, [0, 3]),
                (1, 2, [i for i in range(TOTAL_SHARDS) if i != 6], [6])])
    for stage in stages:
        assert by_name["fleet." + stage], stage
        assert _sample(c, 'SeaweedFS_fleet_stage_seconds_count'
                          '{stage="%s"}' % stage) > counts[stage]
    for name in ("fleet.wait.reader", "fleet.wait.retire_slot",
                 "fleet.wait.lane_from_retire", "rs.stage", "rs.place",
                 "rs.wait", "rs.fetch"):
        assert by_name[name], name
