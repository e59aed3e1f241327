"""Shell command tests.

Pure placement planning is tested on fabricated views (the reference's
command_ec_test.go pattern); the EC lifecycle commands run against a
real in-process cluster.
"""

import os

import pytest

from seaweedfs_tpu.ec.shard_bits import ShardBits, TOTAL_SHARDS
from seaweedfs_tpu.operation.file_id import parse_fid
from seaweedfs_tpu.shell import Shell, ec_common
from seaweedfs_tpu.shell.command_env import EcNode
from seaweedfs_tpu.shell.command_volume import (plan_fix_replication,
                                                plan_volume_balance)
from tests.cluster_util import Cluster

# -- pure planning -------------------------------------------------------------


def test_balanced_distribution_favors_free_slots():
    nodes = [EcNode("a:1", 10, {}), EcNode("b:1", 3, {}),
             EcNode("c:1", 1, {})]
    plan = ec_common.balanced_distribution(nodes)
    assert sum(len(s) for s in plan.values()) == TOTAL_SHARDS
    assert sorted(sid for s in plan.values() for sid in s) == \
        list(range(TOTAL_SHARDS))
    assert len(plan["a:1"]) > len(plan["b:1"]) > len(plan["c:1"])


def test_balanced_distribution_single_node_takes_all():
    plan = ec_common.balanced_distribution([EcNode("a:1", 50, {})])
    assert plan == {"a:1": list(range(TOTAL_SHARDS))}


def test_plan_dedupe_keeps_least_loaded_copy():
    nodes = [
        EcNode("a:1", 5, {7: ShardBits.of(0, 1, 2, 3)}),
        EcNode("b:1", 5, {7: ShardBits.of(0)}),
    ]
    deletes = ec_common.plan_dedupe(nodes)
    # shard 0 duplicated; the copy on the busier node (a) goes
    assert deletes == [(7, 0, "a:1")]


def test_plan_balance_evens_counts():
    nodes = [
        EcNode("a:1", 5, {1: ShardBits.of(*range(10))}),
        EcNode("b:1", 5, {1: ShardBits.of(10, 11, 12, 13)}),
        EcNode("c:1", 5, {}),
    ]
    moves = ec_common.plan_balance(nodes)
    counts = {"a:1": 10, "b:1": 4, "c:1": 0}
    for mv in moves:
        counts[mv.src] -= len(mv.shard_ids)
        counts[mv.dst] += len(mv.shard_ids)
    assert max(counts.values()) - min(counts.values()) <= 1
    # no move may duplicate a shard on its destination
    held = {"a:1": set(range(10)), "b:1": {10, 11, 12, 13}, "c:1": set()}
    for mv in moves:
        for sid in mv.shard_ids:
            assert sid not in held[mv.dst]
            held[mv.src].discard(sid)
            held[mv.dst].add(sid)


def test_missing_shards():
    nodes = [EcNode("a:1", 5, {3: ShardBits.of(*range(12))})]
    assert ec_common.missing_shards(nodes, 3) == [12, 13]


def test_plan_volume_balance():
    counts = {"a:1": [1, 2, 3, 4, 5, 6], "b:1": [7], "c:1": []}
    maxes = {"a:1": 10, "b:1": 10, "c:1": 10}
    moves = plan_volume_balance(counts, maxes)
    final = {u: len(v) for u, v in counts.items()}
    for mv in moves:
        final[mv.src] -= 1
        final[mv.dst] += 1
    assert max(final.values()) - min(final.values()) <= 1


def test_plan_fix_replication():
    from seaweedfs_tpu.shell.command_volume import NodeLoc
    a = NodeLoc("a:1", "dc1", "r1")
    b = NodeLoc("b:1", "dc1", "r1")
    # vid 5 wants 2 copies (placement 001 -> byte 1) but has 1
    replicas = {5: [(a, 1)], 6: [(a, 0)]}
    fixes = plan_fix_replication(replicas, [a, b])
    assert fixes == [(5, "a:1", "b:1")]


def test_plan_fix_replication_honors_placement():
    """Placement 110 = one copy in another DC + one in another rack of
    the same DC; the planner must pick those, not same-rack peers."""
    from seaweedfs_tpu.shell.command_volume import NodeLoc
    a = NodeLoc("a:1", "dc1", "r1")
    same_rack = NodeLoc("b:1", "dc1", "r1")
    other_rack = NodeLoc("c:1", "dc1", "r2")
    other_dc = NodeLoc("d:1", "dc2", "r1")
    fixes = plan_fix_replication(
        {9: [(a, 110)]}, [a, same_rack, other_rack, other_dc])
    dsts = {mv.dst for mv in fixes}
    assert dsts == {"c:1", "d:1"}       # NOT the same-rack b:1


def test_plan_fix_replication_partial_progress():
    """001 needs a same-rack peer; with none available nothing is
    planned rather than violating the grammar."""
    from seaweedfs_tpu.shell.command_volume import NodeLoc
    a = NodeLoc("a:1", "dc1", "r1")
    other_rack = NodeLoc("c:1", "dc1", "r2")
    fixes = plan_fix_replication({9: [(a, 1)]}, [a, other_rack])
    assert fixes == []


def test_plan_balance_across_racks():
    """One volume's 14 shards piled into one rack must spread so no
    rack holds more than ceil(14/racks)."""
    from seaweedfs_tpu.shell import ec_common
    nodes = [
        EcNode("a:1", 20, {1: ShardBits.of(*range(10))}, rack="dc/r1"),
        EcNode("b:1", 20, {1: ShardBits.of(10, 11, 12, 13)},
               rack="dc/r1"),
        EcNode("c:1", 20, {}, rack="dc/r2"),
        EcNode("d:1", 20, {}, rack="dc/r3"),
    ]
    moves = ec_common.plan_balance_across_racks(nodes)
    after = ec_common.apply_moves_to_nodes(nodes, moves)
    per_rack = {}
    held = {}
    for n in after:
        bits = n.shards.get(1, ShardBits(0))
        per_rack[n.rack] = per_rack.get(n.rack, 0) + bits.count
        for sid in bits.shard_ids:
            assert sid not in held, f"shard {sid} duplicated"
            held[sid] = n.url
    assert len(held) == 14              # nothing lost
    assert max(per_rack.values()) <= 5  # ceil(14/3)


# -- live cluster --------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = Cluster(tmp_path_factory.mktemp("shellcluster"), n_volume_servers=3)
    yield c
    c.stop()


@pytest.fixture()
def shell(cluster):
    return Shell(cluster.master.url)


def _fill_volume(cluster, collection, n=5, size=2048):
    datas = [os.urandom(size) for _ in range(n)]
    fids = [cluster.upload(d, collection=collection) for d in datas]
    vid = parse_fid(fids[0]).volume_id
    keep = [(f, d) for f, d in zip(fids, datas)
            if parse_fid(f).volume_id == vid]
    return vid, keep


def test_shell_help_lists_commands(shell):
    txt = shell.run_command("help")
    for name in ("ec.encode", "ec.rebuild", "ec.balance", "ec.decode",
                 "volume.balance", "volume.fix.replication", "volume.list"):
        assert name in txt


def test_ec_encode_spreads_and_serves(cluster, shell):
    vid, keep = _fill_volume(cluster, "shenc")
    out = shell.run_command(f"ec.encode -volumeId={vid} -encoder=numpy")
    assert "done" in out
    # shards spread across several nodes
    bits = cluster.wait_for(lambda: cluster.master.topo.lookup_ec(vid),
                            what="ec registration")
    assert len(bits) >= 2, f"expected spread, got {bits}"
    total = ShardBits(0)
    for b in bits.values():
        total = total.plus(b)
    assert total.count == TOTAL_SHARDS
    # original volume is gone; reads go through EC
    assert cluster.master.topo.lookup(vid, "shenc") == []
    for fid, d in keep:
        with cluster.fetch(fid) as r:
            assert r.read() == d


def test_ec_rebuild_after_loss(cluster, shell):
    vid, keep = _fill_volume(cluster, "shreb")
    shell.run_command(f"ec.encode -volumeId={vid} -encoder=numpy")
    cluster.wait_for(lambda: cluster.master.topo.lookup_ec(vid),
                     what="ec registration")

    # lose up to 4 shards (the RS(10,4) tolerance) from one holder
    from seaweedfs_tpu.pb import volume_server_pb2, volume_stub
    bits = cluster.master.topo.lookup_ec(vid)
    victim_url, victim_bits = next(iter(bits.items()))
    lost = victim_bits.shard_ids[:4]
    stub = volume_stub(victim_url)
    stub.VolumeEcShardsUnmount(volume_server_pb2.VolumeEcShardsUnmountRequest(
        volume_id=vid, shard_ids=lost))
    stub.VolumeEcShardsDelete(volume_server_pb2.VolumeEcShardsDeleteRequest(
        volume_id=vid, collection="shreb", shard_ids=lost))
    def loss_visible():
        b = cluster.master.topo.lookup_ec(vid).get(victim_url)
        return b is None or not any(b.has(s) for s in lost)
    cluster.wait_for(loss_visible, what="shard loss visible")

    out = shell.run_command("ec.rebuild -encoder=numpy")
    assert f"volume {vid}" in out

    def all_back():
        total = ShardBits(0)
        for b in cluster.master.topo.lookup_ec(vid).values():
            total = total.plus(b)
        return total.count == TOTAL_SHARDS
    cluster.wait_for(all_back, what="all 14 shards back")
    for fid, d in keep:
        with cluster.fetch(fid) as r:
            assert r.read() == d


def test_ec_balance_dry_run_then_apply(cluster, shell):
    out = shell.run_command("ec.balance")
    assert "dry run" in out
    out = shell.run_command("ec.balance -apply")
    assert "dry run" not in out


def test_bad_flags_keep_shell_alive(shell):
    from seaweedfs_tpu.shell import CommandError
    with pytest.raises(CommandError):
        shell.run_command("ec.encode -notAFlag")
    assert "ec.encode" in shell.run_command("help")


def test_ec_decode_roundtrip(cluster, shell):
    vid, keep = _fill_volume(cluster, "shdec")
    shell.run_command(f"ec.encode -volumeId={vid} -encoder=numpy")
    cluster.wait_for(lambda: cluster.master.topo.lookup_ec(vid),
                     what="ec registration")
    out = shell.run_command(f"ec.decode -volumeId={vid}")
    assert "decoded" in out
    cluster.wait_for(lambda: cluster.master.topo.lookup(vid, "shdec"),
                     what="normal volume back")
    cluster.wait_for(lambda: not cluster.master.topo.lookup_ec(vid),
                     what="ec shards unregistered")
    for fid, d in keep:
        with cluster.fetch(fid) as r:
            assert r.read() == d


def test_volume_fix_replication_restores_copy(cluster, shell):
    data = os.urandom(512)
    fid = cluster.upload(data, replication="001")
    vid = parse_fid(fid).volume_id
    locs = cluster.wait_for(
        lambda: (len(cluster.master.lookup_locations(vid)) == 2
                 and cluster.master.lookup_locations(vid)),
        what="two replicas")
    # drop one replica
    from seaweedfs_tpu.pb import volume_server_pb2, volume_stub
    volume_stub(locs[1][0]).VolumeDelete(
        volume_server_pb2.VolumeDeleteRequest(volume_id=vid))
    cluster.wait_for(
        lambda: len(cluster.master.lookup_locations(vid)) == 1,
        what="replica loss visible")
    out = shell.run_command("volume.fix.replication")
    assert f"volume {vid}" in out
    cluster.wait_for(
        lambda: len(cluster.master.lookup_locations(vid)) == 2,
        what="replica restored")
    with cluster.fetch(fid) as r:
        assert r.read() == data


def test_volume_list_and_cluster_status(cluster, shell):
    assert "DataNode" in shell.run_command("volume.list")
    assert "master:" in shell.run_command("cluster.status")


def test_command_error_preserves_partial_output(shell):
    """A command failing mid-run must still surface what it already
    did (regression: the audit trail used to be swallowed)."""
    from seaweedfs_tpu.shell import COMMANDS, CommandError, command

    @command("test.partial", "writes then explodes")
    def _partial(env, argv, out):
        out.write("step 1 done\n")
        raise RuntimeError("boom")

    try:
        with pytest.raises(CommandError) as ei:
            shell.run_command("test.partial")
        assert ei.value.partial == "step 1 done\n"
        assert "boom" in str(ei.value)
    finally:
        COMMANDS.pop("test.partial", None)


def test_volume_move_fences_writes(cluster, shell):
    """volume.move must mark the source readonly before copying and
    leave the destination writable (regression: a write racing the
    copy used to be lost silently)."""
    from seaweedfs_tpu.operation import operations
    fid = cluster.upload(b"move me")
    vid = parse_fid(fid).volume_id
    locs = operations.lookup(cluster.master.url, vid)
    src = locs[0]
    # dst must not hold ANY replica of vid: the shared module cluster
    # may carry replicated volumes from earlier tests, and VolumeCopy
    # to a server already holding the volume correctly fails
    dst = next(vs.url for vs in cluster.volume_servers
               if vs.url not in locs)
    shell.run_command(f"volume.move -volumeId={vid} "
                      f"-source={src} -target={dst}")
    cluster.wait_for(
        lambda: operations.lookup(cluster.master.url, vid) == [dst],
        what="master sees the move")
    assert operations.download(cluster.master.url, fid) == b"move me"
    # destination must accept writes again
    dst_vs = next(vs for vs in cluster.volume_servers if vs.url == dst)
    assert not dst_vs.store.find_volume(vid).read_only


# -- evacuate / leave / copy / configure.replication ---------------------------


def test_plan_server_evacuation():
    from seaweedfs_tpu.shell.command_volume import plan_server_evacuation
    counts = {"a:1": [1, 2, 3], "b:1": [4], "c:1": [2]}
    maxes = {"a:1": 10, "b:1": 10, "c:1": 10}
    moves, stuck = plan_server_evacuation(counts, maxes, "a:1")
    assert not stuck
    assert {mv.vid for mv in moves} == {1, 2, 3}
    for mv in moves:
        assert mv.src == "a:1" and mv.dst in ("b:1", "c:1")
    # volume 2 already lives on c -> it must land on b
    assert next(mv for mv in moves if mv.vid == 2).dst == "b:1"


def test_plan_server_evacuation_stuck_when_no_room():
    from seaweedfs_tpu.shell.command_volume import plan_server_evacuation
    # every other node already holds vid 9
    counts = {"a:1": [9], "b:1": [9]}
    moves, stuck = plan_server_evacuation(counts, {"a:1": 10, "b:1": 10},
                                          "a:1")
    assert moves == [] and stuck == [9]


def test_plan_ec_evacuation():
    from seaweedfs_tpu.shell.command_volume import plan_ec_evacuation
    nodes = [
        EcNode("a:1", 5, {7: ShardBits.of(0, 1, 2)}),
        EcNode("b:1", 5, {7: ShardBits.of(0)}),
        EcNode("c:1", 5, {}),
    ]
    moves, stuck = plan_ec_evacuation(nodes, "a:1")
    assert not stuck
    moved = {sid for mv in moves for sid in mv.shard_ids}
    assert moved == {0, 1, 2}
    # shard 0 already on b -> must land on c
    dst_of = {sid: mv.dst for mv in moves for sid in mv.shard_ids}
    assert dst_of[0] == "c:1"
    # moves are grouped: at most one ShardMove per (vid, dst)
    assert len(moves) == len({(mv.vid, mv.dst) for mv in moves})


def test_plan_ec_evacuation_respects_free_slots():
    from seaweedfs_tpu.shell.command_volume import plan_ec_evacuation
    nodes = [
        EcNode("a:1", 5, {7: ShardBits.of(0, 1)}),
        EcNode("b:1", 1, {}),   # room for one shard only
        EcNode("c:1", 0, {}),   # full
    ]
    moves, stuck = plan_ec_evacuation(nodes, "a:1")
    assert sum(len(mv.shard_ids) for mv in moves) == 1
    assert all(mv.dst == "b:1" for mv in moves)
    assert stuck == [(7, 1)]


def test_volume_copy_creates_replica(cluster, shell):
    from seaweedfs_tpu.operation import operations
    fid = cluster.upload(b"copy me")
    vid = parse_fid(fid).volume_id
    locs = operations.lookup(cluster.master.url, vid)
    src = locs[0]
    # dst must not hold ANY replica of vid: the shared module cluster
    # may carry replicated volumes from earlier tests, and VolumeCopy
    # to a server already holding the volume correctly fails
    dst = next(vs.url for vs in cluster.volume_servers
               if vs.url not in locs)
    shell.run_command(f"volume.copy -volumeId={vid} "
                      f"-source={src} -target={dst}")
    cluster.wait_for(
        lambda: set(operations.lookup(cluster.master.url, vid)) ==
        {src, dst}, what="master sees both replicas")
    dst_vs = next(vs for vs in cluster.volume_servers if vs.url == dst)
    n = dst_vs.store.read_needle(vid, _needle_for(fid))
    assert bytes(n.data) == b"copy me"


def _needle_for(fid):
    from seaweedfs_tpu.operation.file_id import parse_fid
    from seaweedfs_tpu.storage.needle import Needle
    f = parse_fid(fid)
    return Needle(id=f.key, cookie=f.cookie)


def test_volume_configure_replication(cluster, shell):
    fid = cluster.upload(b"reconf")
    vid = parse_fid(fid).volume_id
    out = shell.run_command(
        f"volume.configure.replication -volumeId={vid} -replication=001")
    assert "replication -> 001" in out

    def placement_seen():
        for _, _, dn in _shell_env(shell).data_nodes(
                _shell_env(shell).topology()):
            for vi in dn.volume_infos:
                if vi.id == vid:
                    return vi.replica_placement == 1
        return False
    cluster.wait_for(placement_seen, what="new placement in heartbeat")
    # on-disk superblock really changed
    vs = next(v for v in cluster.volume_servers
              if v.store.find_volume(vid) is not None)
    assert str(vs.store.find_volume(vid).replica_placement) == "001"
    # idempotent second run
    out = shell.run_command(
        f"volume.configure.replication -volumeId={vid} -replication=001")
    assert "nothing to change" in out


def _shell_env(shell):
    return shell.env


def test_volume_server_evacuate_and_leave(tmp_path):
    from seaweedfs_tpu.operation import operations
    c = Cluster(tmp_path, n_volume_servers=3)
    try:
        sh = Shell(c.master.url)
        fids = [c.upload(os.urandom(512)) for _ in range(6)]
        victim = operations.lookup(
            c.master.url, parse_fid(fids[0]).volume_id)[0]
        out = sh.run_command(f"volumeServer.evacuate -node={victim}")
        assert "dry run" in out
        out = sh.run_command(
            f"volumeServer.evacuate -node={victim} -skipNonMoveable -force")
        vs = next(v for v in c.volume_servers if v.url == victim)

        def drained():
            hb = vs.store.collect_heartbeat()
            return not hb["volumes"] and not hb["ec_shards"]
        c.wait_for(drained, what="victim drained")
        for fid in fids:  # every blob still readable
            assert operations.download(c.master.url, fid)
        sh.run_command(f"volumeServer.leave -node={victim}")
        c.wait_for(
            lambda: victim not in c.master.topo.nodes(),
            what="master forgets the node")
    finally:
        c.stop()


def test_volume_move_preserves_readonly(cluster, shell):
    """A sealed volume must stay sealed after volume.move (regression:
    the destination was unconditionally marked writable)."""
    from seaweedfs_tpu.operation import operations
    fid = cluster.upload(b"sealed blob")
    vid = parse_fid(fid).volume_id
    locs = operations.lookup(cluster.master.url, vid)
    src = locs[0]
    # dst must not hold ANY replica of vid: the shared module cluster
    # may carry replicated volumes from earlier tests, and VolumeCopy
    # to a server already holding the volume correctly fails
    dst = next(vs.url for vs in cluster.volume_servers
               if vs.url not in locs)
    shell.run_command(f"volume.mark -volumeId={vid} -readonly")

    def seen_readonly():
        for _, _, dn in shell.env.data_nodes(shell.env.topology()):
            for vi in dn.volume_infos:
                if vi.id == vid and vi.read_only:
                    return True
        return False
    cluster.wait_for(seen_readonly, what="readonly visible in topology")
    shell.run_command(f"volume.move -volumeId={vid} "
                      f"-source={src} -target={dst}")
    dst_vs = next(vs for vs in cluster.volume_servers if vs.url == dst)
    assert dst_vs.store.find_volume(vid).read_only
    # under full-suite load the heartbeat delta that tells the master
    # about the moved copy can lag the VolumeDelete on src; reading
    # before the master catches up sees "no locations" (30s: the 5s
    # pulse can slip several periods when the single core is saturated)
    seen = []

    # the shared module cluster may have handed out a REPLICATED volume
    # (earlier tests leave some): the other replicas stay where they
    # are, so the move is "dst in, src out", not "exactly [dst]"
    others = [u for u in locs if u != src]

    def moved():
        seen[:] = operations.lookup(cluster.master.url, vid)
        return sorted(seen) == sorted(others + [dst])
    try:
        cluster.wait_for(moved, timeout=30, what="master sees the move")
    except TimeoutError as e:
        raise TimeoutError(
            f"{e}: lookup says {seen}, want {others + [dst]} "
            f"(moved from {src})") from None
    assert operations.download(cluster.master.url, fid) == b"sealed blob"


def test_plan_balance_no_pingpong_on_odd_totals():
    """3-vs-2 shards across two nodes is balanced; the planner must
    not oscillate a shard between them (regression: the live
    ec.balance executed 5 wasteful back-and-forth moves)."""
    nodes = [
        EcNode("a:1", 5, {1: ShardBits.of(0, 1, 2)}),
        EcNode("b:1", 5, {1: ShardBits.of(3, 4)}),
    ]
    assert ec_common.plan_balance(nodes) == []
    # a genuine imbalance still planned, and it converges
    nodes = [
        EcNode("a:1", 5, {1: ShardBits.of(0, 1, 2, 3)}),
        EcNode("b:1", 5, {}),
    ]
    moves = ec_common.plan_balance(nodes)
    assert len(moves) == 2
    assert all(mv.src == "a:1" and mv.dst == "b:1" for mv in moves)


def test_plan_balance_across_racks_respects_free_slots():
    """The only under-cap rack has a full node: the planner must not
    overfill it (regression: free_slots were ignored)."""
    nodes = [
        EcNode("a:1", 20, {1: ShardBits.of(*range(14))}, rack="dc/r1"),
        EcNode("b:1", 0, {}, rack="dc/r2"),     # full disk
        EcNode("c:1", 3, {}, rack="dc/r3"),
    ]
    moves = ec_common.plan_balance_across_racks(nodes)
    to_b = sum(len(mv.shard_ids) for mv in moves if mv.dst == "b:1")
    to_c = sum(len(mv.shard_ids) for mv in moves if mv.dst == "c:1")
    assert to_b == 0
    assert 0 < to_c <= 3


def test_plan_balance_respects_free_slots():
    """The within-rack pass must not plan moves onto full nodes."""
    nodes = [
        EcNode("a:1", 5, {1: ShardBits.of(*range(10))}),
        EcNode("b:1", 0, {}),   # full disk
    ]
    assert ec_common.plan_balance(nodes) == []


def test_plan_balance_across_racks_duplicated_first_shard():
    """A duplicated first shard id must not strand the rack: the
    planner has to fall back to the holder's other shards."""
    nodes = [
        EcNode("a:1", 20, {1: ShardBits.of(0, 1, 2, 3)}, rack="dc/r1"),
        # both under-cap nodes already hold shard 0 (pre-dedupe view)
        EcNode("b:1", 20, {1: ShardBits.of(0)}, rack="dc/r2"),
        EcNode("c:1", 20, {1: ShardBits.of(0)}, rack="dc/r3"),
    ]
    moves = ec_common.plan_balance_across_racks(nodes)
    moved = {sid for mv in moves for sid in mv.shard_ids}
    assert moved and 0 not in moved       # fell back past shard 0
