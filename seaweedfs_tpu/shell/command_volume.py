"""Volume ops-plane commands: list, balance, fix.replication, vacuum,
move, mount/unmount, mark, delete.

Reference: weed/shell/command_volume_*.go. Balance/fix planning is
pure over the TopologyInfo snapshot (testable on fabricated views).
"""

from __future__ import annotations

import argparse
import posixpath
from typing import Dict, List, NamedTuple, Optional, Tuple

from seaweedfs_tpu.ec.shard_bits import ShardBits
from seaweedfs_tpu.pb import master_pb2, volume_server_pb2
from seaweedfs_tpu.shell import command
from seaweedfs_tpu.shell.command_env import CommandEnv
from seaweedfs_tpu.storage.superblock import ReplicaPlacement


class VolumeMove(NamedTuple):
    vid: int
    src: str
    dst: str


def plan_volume_balance(counts: Dict[str, List[int]],
                        max_counts: Dict[str, int]) -> List[VolumeMove]:
    """counts: url -> vids held. Move volumes from fullest to emptiest
    (by used/max ratio) until within one volume of balance."""
    urls = list(counts)
    if len(urls) < 2:
        return []
    held = {u: list(v) for u, v in counts.items()}
    moves: List[VolumeMove] = []

    def ratio(u):
        return len(held[u]) / max(1, max_counts.get(u, 8))

    for _ in range(sum(len(v) for v in held.values())):
        src = max(urls, key=ratio)
        dst = min(urls, key=ratio)
        if src == dst or len(held[src]) - len(held[dst]) <= 1:
            break
        movable = [v for v in held[src] if v not in held[dst]]
        if not movable:
            break
        vid = movable[0]
        held[src].remove(vid)
        held[dst].append(vid)
        moves.append(VolumeMove(vid, src, dst))
    return moves


class NodeLoc(NamedTuple):
    """Where a node lives, for placement-aware planning."""
    url: str
    dc: str = ""
    rack: str = ""


def _placement_deficit(rp: ReplicaPlacement, primary: NodeLoc,
                       others: List[NodeLoc]):
    """(dx, dy, dz) still needed with `primary` as the first copy, or
    None when the existing layout over-fills a dimension."""
    x = sum(1 for o in others if o.dc != primary.dc)
    y = sum(1 for o in others
            if o.dc == primary.dc and o.rack != primary.rack)
    z = sum(1 for o in others
            if o.dc == primary.dc and o.rack == primary.rack)
    dx, dy, dz = rp.diff_dc - x, rp.diff_rack - y, rp.same_rack - z
    if min(dx, dy, dz) < 0:
        return None
    return dx, dy, dz


def plan_fix_replication(
        replicas_by_vid: Dict[int, List[Tuple[NodeLoc, int]]],
        candidates: List[NodeLoc]) -> List[VolumeMove]:
    """replicas_by_vid: vid -> [(holder location, placement_byte)].
    Placement-aware (reference command_volume_fix_replication.go):
    missing copies go where the xyz grammar wants them — same rack,
    other racks of the same DC, or other DCs — not just anywhere."""
    fixes = []
    for vid, replicas in sorted(replicas_by_vid.items()):
        rp = ReplicaPlacement.from_byte(replicas[0][1])
        holders = [loc for loc, _ in replicas]
        if len(holders) >= rp.copy_count:
            continue
        held_urls = {h.url for h in holders}
        # any primary with a non-negative deficit works (every valid
        # primary's deficit sums to copy_count - len(holders))
        best = next(
            ((p, d) for p in holders
             if (d := _placement_deficit(
                 rp, p, [h for h in holders if h is not p]))
             is not None),
            None)
        if best is None:
            continue   # existing layout already violates rp; skip
        primary, (dx, dy, dz) = best
        free = [c for c in candidates if c.url not in held_urls]

        def take(pred, n):
            nonlocal free
            picked = [c for c in free if pred(c)][:n]
            free = [c for c in free if c not in picked]
            return picked

        targets = (
            take(lambda c: c.dc == primary.dc
                 and c.rack == primary.rack, dz)
            + take(lambda c: c.dc == primary.dc
                   and c.rack != primary.rack, dy)
            + take(lambda c: c.dc != primary.dc, dx))
        for dst in targets:
            fixes.append(VolumeMove(vid, primary.url, dst.url))
    return fixes


@command("volume.list", "show the topology tree")
def volume_list(env: CommandEnv, argv: List[str], out) -> None:
    topo = env.topology()
    out.write(f"Topology volumes:{topo.volume_count} "
              f"max:{topo.max_volume_count} "
              f"free:{topo.free_volume_count}\n")
    for dc in topo.data_center_infos:
        out.write(f"  DataCenter {dc.id}\n")
        for rack in dc.rack_infos:
            out.write(f"    Rack {rack.id}\n")
            for dn in rack.data_node_infos:
                out.write(f"      DataNode {dn.id} "
                          f"volumes:{dn.volume_count} "
                          f"max:{dn.max_volume_count}\n")
                for vi in dn.volume_infos:
                    out.write(f"        volume id:{vi.id} "
                              f"size:{vi.size} "
                              f"collection:{vi.collection!r} "
                              f"files:{vi.file_count} "
                              f"deleted:{vi.delete_count} "
                              f"ro:{vi.read_only}\n")
                for e in dn.ec_shard_infos:
                    from seaweedfs_tpu.ec.shard_bits import ShardBits
                    out.write(f"        ec volume id:{e.id} "
                              f"collection:{e.collection!r} "
                              f"shards:{ShardBits(e.ec_index_bits).shard_ids}\n")


@command("volume.balance", "move volumes so servers are evenly loaded")
def volume_balance(env: CommandEnv, argv: List[str], out) -> None:
    p = argparse.ArgumentParser(prog="volume.balance")
    p.add_argument("-collection", default="",
                   help="restrict to one collection ('' = all)")
    args = p.parse_args(argv)
    env.acquire_lock()
    try:
        topo = env.topology()
        counts: Dict[str, List[int]] = {}
        max_counts: Dict[str, int] = {}
        for _, _, dn in env.data_nodes(topo):
            vids = [vi.id for vi in dn.volume_infos
                    if not args.collection
                    or vi.collection == args.collection]
            counts[dn.id] = vids
            max_counts[dn.id] = int(dn.max_volume_count)
        readonly = _readonly_vids(env, topo)
        for mv in plan_volume_balance(counts, max_counts):
            _move_volume(env, mv, out, was_readonly=mv.vid in readonly)
    finally:
        env.release_lock()


def _move_volume(env: CommandEnv, mv: VolumeMove, out,
                 was_readonly: bool = False) -> None:
    """freeze writes on src, copy to dst (pull from src), delete from
    src, unfreeze on dst — the reference's volume.move ordering
    (command_volume_move.go). Without the readonly fence a write landing
    on src between copy and delete would be lost. A volume that was
    sealed before the move stays sealed on the destination."""
    env.volume_server(mv.src).VolumeMarkReadonly(
        volume_server_pb2.VolumeMarkReadonlyRequest(volume_id=mv.vid))
    try:
        env.volume_server(mv.dst).VolumeCopy(
            volume_server_pb2.VolumeCopyRequest(
                volume_id=mv.vid, source_data_node=mv.src))
    except Exception:
        if not was_readonly:
            # copy failed: unfreeze the source so it keeps serving writes
            env.volume_server(mv.src).VolumeMarkWritable(
                volume_server_pb2.VolumeMarkWritableRequest(
                    volume_id=mv.vid))
        raise
    if was_readonly:
        # seal the destination BEFORE the source copy disappears: a
        # write sneaking in between VolumeDelete and a late re-mark
        # would land on a volume that must stay sealed
        env.volume_server(mv.dst).VolumeMarkReadonly(
            volume_server_pb2.VolumeMarkReadonlyRequest(volume_id=mv.vid))
    env.volume_server(mv.src).VolumeDelete(
        volume_server_pb2.VolumeDeleteRequest(volume_id=mv.vid))
    if not was_readonly:
        env.volume_server(mv.dst).VolumeMarkWritable(
            volume_server_pb2.VolumeMarkWritableRequest(volume_id=mv.vid))
    out.write(f"volume {mv.vid}: moved {mv.src} -> {mv.dst}\n")


def _readonly_vids(env: CommandEnv, topo=None) -> set:
    """vids with any replica flagged readonly in the heartbeat view."""
    topo = topo or env.topology()
    return {vi.id for _, _, dn in env.data_nodes(topo)
            for vi in dn.volume_infos if vi.read_only}


@command("volume.move", "move one volume between servers")
def volume_move(env: CommandEnv, argv: List[str], out) -> None:
    p = argparse.ArgumentParser(prog="volume.move")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-source", required=True)
    p.add_argument("-target", required=True)
    args = p.parse_args(argv)
    env.acquire_lock()
    try:
        _move_volume(env, VolumeMove(args.volumeId, args.source,
                                     args.target), out,
                     was_readonly=args.volumeId in _readonly_vids(env))
    finally:
        env.release_lock()


@command("volume.fix.replication", "re-create missing replicas")
def volume_fix_replication(env: CommandEnv, argv: List[str], out) -> None:
    env.acquire_lock()
    try:
        topo = env.topology()
        replicas: Dict[int, List[Tuple[NodeLoc, int]]] = {}
        locs = []
        for dc, rack, dn in env.data_nodes(topo):
            loc = NodeLoc(dn.id, dc, rack)
            locs.append(loc)
            for vi in dn.volume_infos:
                replicas.setdefault(vi.id, []).append(
                    (loc, vi.replica_placement))
        fixes = plan_fix_replication(replicas, locs)
        for mv in fixes:
            env.volume_server(mv.dst).VolumeCopy(
                volume_server_pb2.VolumeCopyRequest(
                    volume_id=mv.vid, source_data_node=mv.src))
            out.write(f"volume {mv.vid}: replicated {mv.src} -> "
                      f"{mv.dst}\n")
        if not fixes:
            out.write("all volumes sufficiently replicated\n")
    finally:
        env.release_lock()


def plan_server_evacuation(
        counts: Dict[str, List[int]], max_counts: Dict[str, int],
        server: str) -> Tuple[List[VolumeMove], List[int]]:
    """Plan moving every volume off `server`. Each volume goes to the
    least-loaded other node not already holding a replica of it
    (reference command_volume_server_evacuate.go moveAwayOneNormalVolume).
    Returns (moves, unmoveable_vids)."""
    if server not in counts:
        raise ValueError(f"{server} is not in this cluster")
    held = {u: list(v) for u, v in counts.items()}
    moves: List[VolumeMove] = []
    stuck: List[int] = []
    others = [u for u in counts if u != server]
    for vid in list(held[server]):
        candidates = [u for u in others
                      if vid not in held[u]
                      and len(held[u]) < max_counts.get(u, 8)]
        if not candidates:
            stuck.append(vid)
            continue
        dst = min(candidates,
                  key=lambda u: len(held[u]) / max(1, max_counts.get(u, 8)))
        held[server].remove(vid)
        held[dst].append(vid)
        moves.append(VolumeMove(vid, server, dst))
    return moves, stuck


def plan_ec_evacuation(nodes, server: str):
    """Plan moving every EC shard off `server`: each shard to the other
    node with the fewest total shards that doesn't hold that shard and
    still has free slots (reference command_volume_server_evacuate.go
    evacuateEcVolumes). Moves are grouped per (vid, dst) so the
    executor copies the .ecx once and batches the 4 lifecycle RPCs."""
    from seaweedfs_tpu.shell.ec_common import ShardMove
    by_url = {n.url: n for n in nodes}
    if server not in by_url:
        return [], []
    this, others = by_url[server], [n for n in nodes if n.url != server]
    loads = {n.url: n.shard_count() for n in others}
    room = {n.url: max(n.free_slots, 0) for n in others}
    grouped: Dict[Tuple[int, str], List[int]] = {}
    stuck = []
    for vid, bits in sorted(this.shards.items()):
        for sid in bits.shard_ids:
            candidates = [n for n in others
                          if room[n.url] > 0
                          and sid not in n.shards.get(vid, ShardBits(0)
                                                      ).shard_ids]
            if not candidates:
                stuck.append((vid, sid))
                continue
            dst = min(candidates, key=lambda n: loads[n.url])
            loads[dst.url] += 1
            room[dst.url] -= 1
            grouped.setdefault((vid, dst.url), []).append(sid)
    moves = [ShardMove(vid, tuple(sids), server, dst)
             for (vid, dst), sids in sorted(grouped.items())]
    return moves, stuck


@command("volume.copy", "copy a volume from one server to another")
def volume_copy(env: CommandEnv, argv: List[str], out) -> None:
    """Reference: weed/shell/command_volume_copy.go — a plain VolumeCopy
    to the target (the source keeps its replica; use volume.move to
    transfer ownership)."""
    p = argparse.ArgumentParser(prog="volume.copy")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-source", required=True)
    p.add_argument("-target", required=True)
    args = p.parse_args(argv)
    if args.source == args.target:
        raise ValueError("source and target are the same node")
    env.acquire_lock()
    try:
        # Fence writes on the source for the duration of the pull: a
        # needle landing mid-copy would be missing from the new replica
        # while the master serves both locations (same reasoning as
        # _move_volume above). A volume that was already readonly
        # (sealed, tiered) stays that way afterwards.
        was_readonly = any(
            vi.read_only
            for _, _, dn in env.data_nodes(env.topology())
            if dn.id == args.source
            for vi in dn.volume_infos if vi.id == args.volumeId)
        env.volume_server(args.source).VolumeMarkReadonly(
            volume_server_pb2.VolumeMarkReadonlyRequest(
                volume_id=args.volumeId))
        try:
            env.volume_server(args.target).VolumeCopy(
                volume_server_pb2.VolumeCopyRequest(
                    volume_id=args.volumeId,
                    source_data_node=args.source))
        finally:
            if not was_readonly:
                env.volume_server(args.source).VolumeMarkWritable(
                    volume_server_pb2.VolumeMarkWritableRequest(
                        volume_id=args.volumeId))
        out.write(f"volume {args.volumeId}: copied {args.source} -> "
                  f"{args.target}\n")
    finally:
        env.release_lock()


@command("volume.configure.replication",
         "change a volume's replication value")
def volume_configure_replication(env: CommandEnv, argv: List[str],
                                 out) -> None:
    """Reference: weed/shell/command_volume_configure_replication.go —
    rewrite the superblock on every replica whose placement differs;
    follow with volume.fix.replication to actually create the copies."""
    p = argparse.ArgumentParser(prog="volume.configure.replication")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-replication", required=True)
    args = p.parse_args(argv)
    want = ReplicaPlacement.parse(args.replication).to_byte()
    env.acquire_lock()
    try:
        touched = 0
        for _, _, dn in env.data_nodes(env.topology()):
            for vi in dn.volume_infos:
                if vi.id != args.volumeId or vi.replica_placement == want:
                    continue
                resp = env.volume_server(dn.id).VolumeConfigure(
                    volume_server_pb2.VolumeConfigureRequest(
                        volume_id=args.volumeId,
                        replication=args.replication))
                if resp.error:
                    raise RuntimeError(f"{dn.id}: {resp.error}")
                out.write(f"volume {args.volumeId}: replication -> "
                          f"{args.replication} on {dn.id}\n")
                touched += 1
        if not touched:
            out.write(f"volume {args.volumeId}: nothing to change\n")
    finally:
        env.release_lock()


@command("volumeServer.evacuate", "move all data off a volume server")
def volume_server_evacuate(env: CommandEnv, argv: List[str], out) -> None:
    """Reference: weed/shell/command_volume_server_evacuate.go — move
    every normal volume and EC shard to other servers, typically before
    a shutdown or upgrade."""
    p = argparse.ArgumentParser(prog="volumeServer.evacuate")
    p.add_argument("-node", required=True, help="<host:port> to drain")
    p.add_argument("-skipNonMoveable", action="store_true")
    p.add_argument("-force", action="store_true",
                   help="actually apply the changes")
    args = p.parse_args(argv)

    def plan():
        topo = env.topology()
        counts: Dict[str, List[int]] = {}
        max_counts: Dict[str, int] = {}
        for _, _, dn in env.data_nodes(topo):
            counts[dn.id] = [vi.id for vi in dn.volume_infos]
            max_counts[dn.id] = int(dn.max_volume_count)
        moves, stuck = plan_server_evacuation(counts, max_counts,
                                              args.node)
        ec_moves, ec_stuck = plan_ec_evacuation(
            env.collect_ec_nodes(topo), args.node)
        if (stuck or ec_stuck) and not args.skipNonMoveable:
            items = [str(v) for v in stuck] + \
                [f"{vid}.{sid}" for vid, sid in ec_stuck]
            raise RuntimeError(
                f"no destination for: {', '.join(items)} "
                f"(use -skipNonMoveable to move the rest)")
        return topo, moves, stuck, ec_moves, ec_stuck

    if not args.force:
        _, moves, stuck, ec_moves, ec_stuck = plan()
        for mv in moves:
            out.write(f"would move volume {mv.vid} {mv.src} -> {mv.dst}\n")
        for mv in ec_moves:
            out.write(f"would move shards {list(mv.shard_ids)} of "
                      f"volume {mv.vid} {mv.src} -> {mv.dst}\n")
        out.write("dry run; add -force to execute\n")
        return
    env.acquire_lock()
    try:
        # plan under the lock: another admin's move between snapshot and
        # execution would make VolumeCopy abort mid-drain
        from seaweedfs_tpu.shell.command_ec import (_ec_collections,
                                                    apply_shard_move)
        topo, moves, stuck, ec_moves, ec_stuck = plan()
        readonly = _readonly_vids(env, topo)
        for mv in moves:
            _move_volume(env, mv, out, was_readonly=mv.vid in readonly)
        ec_collections = _ec_collections(env)
        for mv in ec_moves:
            apply_shard_move(env, mv, ec_collections.get(mv.vid, ""), out)
        for vid in stuck:
            out.write(f"skipped non-moveable volume {vid}\n")
        for vid, sid in ec_stuck:
            out.write(f"skipped non-moveable shard {vid}.{sid}\n")
    finally:
        env.release_lock()


@command("volumeServer.leave", "ask a volume server to leave the cluster")
def volume_server_leave(env: CommandEnv, argv: List[str], out) -> None:
    """Reference: weed/shell/command_volume_server_leave.go — the server
    stops heartbeating so the master forgets it; its process stays up
    until stopped by the operator."""
    p = argparse.ArgumentParser(prog="volumeServer.leave")
    p.add_argument("-node", required=True, help="<host:port> to remove")
    args = p.parse_args(argv)
    env.volume_server(args.node).VolumeServerLeave(
        volume_server_pb2.VolumeServerLeaveRequest())
    out.write(f"{args.node}: asked to leave\n")


@command("volume.scrub", "start/pause/inspect the background integrity "
                         "scrub")
def volume_scrub(env: CommandEnv, argv: List[str], out) -> None:
    """Control the per-server scrub daemon (seaweedfs_tpu/scrub/):
    start a verification pass (the default), pause a running one, or
    print each server's ledger. Without -node the action fans out to
    every volume server in the topology, and each EC volume is verified
    by one of its holders; a pass started with -node verifies every EC
    volume that server holds. With -wait a start returns
    when the pass has ended on every server, and prints each server's
    ledger and one line a volume the pass covered: `clean`, what it
    rebuilt or rewrote, or `unrecoverable`; a pass that failed fails
    the command."""
    p = argparse.ArgumentParser(prog="volume.scrub")
    p.add_argument("-node", default="",
                   help="<host:port>; all volume servers when empty")
    p.add_argument("-volumeId", type=int, default=0,
                   help="restrict the pass to one volume id")
    p.add_argument("-throttleMBps", type=float, default=0.0,
                   help="IO budget for the pass (0 = server default)")
    p.add_argument("-full", action="store_true",
                   help="reset the ledger and rescan from scratch")
    p.add_argument("-wait", action="store_true",
                   help="return when the started pass has ended, with "
                        "its verdict on every volume it covered")
    g = p.add_mutually_exclusive_group()
    g.add_argument("-pause", action="store_true",
                   help="hold the running pass at the next volume")
    g.add_argument("-status", action="store_true",
                   help="print the scrub ledger instead of starting")
    args = p.parse_args(argv)
    if args.node:
        urls = [args.node]
    else:
        urls = sorted(dn.id for _, _, dn
                      in env.data_nodes(env.topology()))
    started = []   # (url, passes ended when its start came)
    for url in urls:
        stub = env.volume_server(url)
        if args.status:
            _write_scrub_ledger(out, url, stub.VolumeScrubStatus(
                volume_server_pb2.VolumeScrubStatusRequest()))
        elif args.pause:
            r = stub.VolumeScrubPause(
                volume_server_pb2.VolumeScrubPauseRequest())
            out.write(f"{url}: "
                      f"{'paused' if r.paused else 'no scrub running'}\n")
        else:
            r = stub.VolumeScrubStart(
                volume_server_pb2.VolumeScrubStartRequest(
                    volume_ids=[args.volumeId] if args.volumeId else [],
                    throttle_mbps=args.throttleMBps,
                    full=args.full, alone=bool(args.node)))
            out.write(f"{url}: "
                      f"{'scrub started' if r.started else 'scrub already running'}\n")
            started.append((url, r.passes_ended))
    if not args.wait:
        return
    failed = []
    for url, ended in started:   # every server's pass is under way
        st = env.volume_server(url).VolumeScrubStatus(
            volume_server_pb2.VolumeScrubStatusRequest(
                wait=True, after_passes_ended=ended))
        _write_scrub_ledger(out, url, st)
        if st.passes_ended <= ended:
            failed.append(f"{url}: the daemon stopped before the pass ended")
            continue
        for v in st.last_pass.volumes:
            did = []
            if v.rebuilt_shard_ids:
                did.append(f"rebuilt shards {list(v.rebuilt_shard_ids)}")
            if v.needles_repaired:
                did.append(f"rewrote {v.needles_repaired} needle(s)")
            if v.unrecoverable:
                did.append("unrecoverable")
            out.write(f"{url}: volume {v.volume_id}: "
                      f"{', '.join(did) or 'clean'}\n")
        if st.last_pass.failed:
            failed.append(f"{url}: scrub pass failed: {st.last_pass.error}")
    if failed:
        raise RuntimeError("volume.scrub failed: " + "; ".join(failed))


def _write_scrub_ledger(out, url: str, st) -> None:
    out.write(
        f"{url}: {st.state} passes:{st.passes_completed} "
        f"scanned:{st.bytes_scanned}B "
        f"needles:{st.needles_verified} "
        f"stripes:{st.stripes_verified} "
        f"found:{st.corruptions_found} "
        f"repaired:{st.corruptions_repaired} "
        f"unrecoverable:{st.unrecoverable} "
        f"lag:{st.scan_lag_seconds:.0f}s\n")


@command("volume.vacuum", "compact volumes above the garbage threshold")
def volume_vacuum(env: CommandEnv, argv: List[str], out) -> None:
    p = argparse.ArgumentParser(prog="volume.vacuum")
    p.add_argument("-garbageThreshold", type=float, default=0.3)
    args = p.parse_args(argv)
    env.master.VacuumVolume(master_pb2.VacuumVolumeRequest(
        garbage_threshold=args.garbageThreshold))
    out.write("vacuum triggered\n")


def live_keys_from_idx(blob: bytes) -> Dict[int, int]:
    """Replay raw .idx bytes to the live key set: key -> size. Later
    entries win; tombstones (offset 0 / negative size) drop the key —
    the same replay the needle map does at volume load."""
    from seaweedfs_tpu.storage import idx as idx_codec
    from seaweedfs_tpu.storage import types as t
    live: Dict[int, int] = {}
    for off in range(0, len(blob) - len(blob) % t.NEEDLE_MAP_ENTRY_SIZE,
                     t.NEEDLE_MAP_ENTRY_SIZE):
        key, offset, size = idx_codec.parse_entry(
            blob[off:off + t.NEEDLE_MAP_ENTRY_SIZE])
        if offset == 0 or t.size_is_deleted(size):
            live.pop(key, None)
        else:
            live[key] = size
    return live


@command("volume.fsck", "find volume blobs not referenced by the filer")
def volume_fsck(env: CommandEnv, argv: List[str], out) -> None:
    """Cross-check the data plane against the namespace (reference
    command_volume_fsck.go): collect every needle key from every
    volume's index (set A), every chunk fileId referenced by the filer
    incl. manifest expansion (set B), and report A−B as orphans.
    Assumes the whole cluster is used by the one configured filer.
    -reallyDeleteFromVolume purges the orphans via BatchDelete."""
    p = argparse.ArgumentParser(prog="volume.fsck")
    p.add_argument("-v", action="store_true", dest="verbose")
    p.add_argument("-reallyDeleteFromVolume", action="store_true",
                   dest="purge", help="<expert only> delete orphans")
    p.add_argument("-cutoffTimeAgo", type=float, default=300,
                   help="skip purging volumes written within the last "
                        "N seconds (an in-flight upload's chunks look "
                        "like orphans until its CreateEntry lands)")
    args = p.parse_args(argv)
    env.acquire_lock()
    try:
        # set A: vid -> {key: size} from every volume/EC index
        topo = env.topology()
        holders: Dict[int, Tuple[str, str, bool]] = {}
        for _, _, dn in env.data_nodes(topo):
            for vi in dn.volume_infos:
                holders.setdefault(vi.id, (dn.id, vi.collection, False))
            for e in dn.ec_shard_infos:
                holders.setdefault(e.id, (dn.id, e.collection, True))
        volume_keys: Dict[int, Dict[int, int]] = {}
        for vid, (url, collection, is_ec) in sorted(holders.items()):
            blob = b"".join(
                r.file_content for r in env.volume_server(url).CopyFile(
                    volume_server_pb2.CopyFileRequest(
                        volume_id=vid, ext=".ecx" if is_ec else ".idx",
                        collection=collection, is_ec_volume=is_ec)))
            volume_keys[vid] = live_keys_from_idx(blob)
            if args.verbose:
                out.write(f"volume {vid} on {url}: "
                          f"{len(volume_keys[vid])} keys\n")

        # set B: every chunk the filer references, manifests expanded.
        # Unlike resolve_chunk_manifest (which returns only the leaf
        # chunks), every level's fid counts as referenced here — the
        # manifest blob itself is a needle too.
        from seaweedfs_tpu.filer.stream import (fetch_chunk_bytes,
                                                filer_lookup_fn)
        from seaweedfs_tpu.operation.file_id import parse_fid
        from seaweedfs_tpu.pb import filer_pb2 as fpb

        lookup = filer_lookup_fn(env.filer)
        filer_keys: Dict[int, set] = {}
        n_files = 0

        def note(chunks):
            for c in chunks:
                f = parse_fid(c.file_id)
                filer_keys.setdefault(f.volume_id, set()).add(f.key)
                if c.is_chunk_manifest:
                    m = fpb.FileChunkManifest()
                    m.ParseFromString(fetch_chunk_bytes(
                        lookup, c.file_id, bytes(c.cipher_key),
                        c.is_compressed))
                    note(m.chunks)

        def walk(directory: str):
            nonlocal n_files
            for entry in env.list_filer_entries(directory):
                full = posixpath.join(directory, entry.name)
                if entry.is_directory:
                    walk(full)
                else:
                    n_files += 1
                    note(entry.chunks)

        walk("/")
        if args.verbose:
            out.write(f"filer references {n_files} files over "
                      f"{sum(len(s) for s in filer_keys.values())} "
                      f"chunks\n")

        # A − B
        total_orphans = total_orphan_bytes = in_use = 0
        second_pass_keys: Optional[Dict[int, set]] = None

        def rewalk_keys() -> Dict[int, set]:
            """Fresh namespace view taken immediately before purging:
            an upload whose CreateEntry landed after the first walk
            must not have its live chunks deleted (the mtime cutoff
            alone cannot see entries that arrived during the walk)."""
            nonlocal filer_keys, n_files
            saved_keys, saved_n = filer_keys, n_files
            filer_keys, n_files = {}, 0
            try:
                walk("/")
                return filer_keys
            finally:
                filer_keys, n_files = saved_keys, saved_n

        for vid, keys in sorted(volume_keys.items()):
            used = filer_keys.get(vid, set())
            orphans = [k for k in keys if k not in used]
            in_use += len(keys) - len(orphans)
            total_orphans += len(orphans)
            orphan_bytes = sum(keys[k] for k in orphans)
            total_orphan_bytes += orphan_bytes
            if not orphans:
                continue
            out.write(f"volume {vid}: {len(orphans)} orphan blobs "
                      f"({orphan_bytes} bytes)\n")
            if args.verbose:
                for k in orphans:
                    out.write(f"  {vid},{k:x}xxxxxxxx\n")
            if args.purge:
                from seaweedfs_tpu.operation.file_id import format_fid
                url, collection, is_ec = holders[vid]
                if is_ec:
                    out.write(f"volume {vid}: skip purging EC volume\n")
                    continue
                # in-flight-upload guard: a chunk uploaded before the
                # .idx snapshot whose CreateEntry lands after the
                # namespace walk looks like an orphan; don't purge a
                # volume that saw writes within the cutoff window
                status = env.volume_server(url).ReadVolumeFileStatus(
                    volume_server_pb2.ReadVolumeFileStatusRequest(
                        volume_id=vid))
                import time as time_mod
                age = time_mod.time() - status.dat_file_timestamp_seconds
                if age < args.cutoffTimeAgo:
                    out.write(
                        f"volume {vid}: written {age:.0f}s ago, inside "
                        f"-cutoffTimeAgo={args.cutoffTimeAgo:.0f}s — "
                        f"skip purging\n")
                    continue
                if second_pass_keys is None:
                    second_pass_keys = rewalk_keys()
                now_used = second_pass_keys.get(vid, set())
                confirmed = [k for k in orphans if k not in now_used]
                if len(confirmed) != len(orphans):
                    out.write(
                        f"volume {vid}: {len(orphans) - len(confirmed)} "
                        f"orphan(s) became referenced since the first "
                        f"walk — keeping them\n")
                orphans = confirmed
                fids = [format_fid(vid, k, 0) for k in orphans]
                resp = env.volume_server(url).BatchDelete(
                    volume_server_pb2.BatchDeleteRequest(
                        file_ids=fids, skip_cookie_check=True))
                failed = [r for r in resp.results
                          if r.status not in (200, 202, 204)]
                for r in failed:
                    out.write(f"  {r.file_id}: {r.error}\n")
                out.write(f"volume {vid}: purged "
                          f"{len(fids) - len(failed)}/{len(fids)} "
                          f"blobs\n")
        pct = (100.0 * total_orphans /
               max(1, total_orphans + in_use))
        out.write(f"total {in_use} in-use, {total_orphans} orphans "
                  f"({pct:.2f}%, {total_orphan_bytes} bytes)\n")
    finally:
        env.release_lock()


@command("volume.mark", "mark a volume readonly/writable")
def volume_mark(env: CommandEnv, argv: List[str], out) -> None:
    p = argparse.ArgumentParser(prog="volume.mark")
    p.add_argument("-volumeId", type=int, required=True)
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("-readonly", action="store_true")
    g.add_argument("-writable", action="store_true")
    args = p.parse_args(argv)
    for url in env.lookup(args.volumeId):
        if args.readonly:
            env.volume_server(url).VolumeMarkReadonly(
                volume_server_pb2.VolumeMarkReadonlyRequest(
                    volume_id=args.volumeId))
        else:
            env.volume_server(url).VolumeMarkWritable(
                volume_server_pb2.VolumeMarkWritableRequest(
                    volume_id=args.volumeId))
        state = "readonly" if args.readonly else "writable"
        out.write(f"volume {args.volumeId}: {state} on {url}\n")


@command("volume.delete", "delete a volume from a server")
def volume_delete(env: CommandEnv, argv: List[str], out) -> None:
    p = argparse.ArgumentParser(prog="volume.delete")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-node", default="",
                   help="server url; all holders when empty")
    args = p.parse_args(argv)
    urls = [args.node] if args.node else env.lookup(args.volumeId)
    for url in urls:
        env.volume_server(url).VolumeDelete(
            volume_server_pb2.VolumeDeleteRequest(volume_id=args.volumeId))
        out.write(f"volume {args.volumeId}: deleted from {url}\n")


@command("volume.mount", "mount a volume from existing files")
def volume_mount(env: CommandEnv, argv: List[str], out) -> None:
    p = argparse.ArgumentParser(prog="volume.mount")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-node", required=True)
    args = p.parse_args(argv)
    env.volume_server(args.node).VolumeMount(
        volume_server_pb2.VolumeMountRequest(volume_id=args.volumeId))
    out.write(f"volume {args.volumeId}: mounted on {args.node}\n")


@command("volume.unmount", "unmount a volume (files stay)")
def volume_unmount(env: CommandEnv, argv: List[str], out) -> None:
    p = argparse.ArgumentParser(prog="volume.unmount")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-node", required=True)
    args = p.parse_args(argv)
    env.volume_server(args.node).VolumeUnmount(
        volume_server_pb2.VolumeUnmountRequest(volume_id=args.volumeId))
    out.write(f"volume {args.volumeId}: unmounted on {args.node}\n")


@command("volume.tier.upload", "move a sealed volume's .dat (or an EC "
                               "volume's shards) to a storage backend")
def volume_tier_upload(env: CommandEnv, argv: List[str], out) -> None:
    """Reference: weed/shell/command_volume_tier_upload.go — mark the
    volume readonly, then VolumeTierMoveDatToRemote on each holder.
    For an erasure-coded vid the holders are its shard servers and
    each moves its local .ecNN files (the lifecycle COLD leg).

    Idempotent: a holder whose copy is already tiered is SKIPPED
    instead of aborting the remaining-holder loop mid-way — a re-run
    after a partial failure (or the lifecycle policy loop re-freezing
    a volume it forgot across a master restart) finishes the stragglers
    without erroring on the ones that made it."""
    import grpc as _grpc
    p = argparse.ArgumentParser(prog="volume.tier.upload")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-dest", required=True,
                   help="backend name, e.g. s3.default / memory.test")
    p.add_argument("-keepLocalDatFile", action="store_true")
    args = p.parse_args(argv)
    for url in env.lookup(args.volumeId):
        try:
            env.volume_server(url).VolumeMarkReadonly(
                volume_server_pb2.VolumeMarkReadonlyRequest(
                    volume_id=args.volumeId))
        except _grpc.RpcError as e:
            # an EC vid has no normal volume to seal — its shards are
            # sealed by construction; anything else is a real failure
            if e.code() != _grpc.StatusCode.NOT_FOUND:
                raise
        try:
            for resp in env.volume_server(url).VolumeTierMoveDatToRemote(
                    volume_server_pb2.VolumeTierMoveDatToRemoteRequest(
                        volume_id=args.volumeId,
                        destination_backend_name=args.dest,
                        keep_local_dat_file=args.keepLocalDatFile)):
                out.write(f"volume {args.volumeId} on {url}: "
                          f"{resp.processed} bytes -> {args.dest} "
                          f"({resp.processed_percentage:.0f}%)\n")
        except _grpc.RpcError as e:
            if "already tiered" in (e.details() or ""):
                out.write(f"volume {args.volumeId} on {url}: "
                          f"already tiered, skipped\n")
                continue
            raise


@command("volume.lifecycle", "status / pause / force the heat-driven "
                             "lifecycle policy engine")
def volume_lifecycle(env: CommandEnv, argv: List[str], out) -> None:
    """Control plane for the master's lifecycle engine
    (seaweedfs_tpu/lifecycle/): print the state machine's status (the
    default), pause/resume the policy loop, or force one volume
    through a transition (bypasses thresholds and dwell, still honors
    dry-run). Talks to the master's /cluster/lifecycle endpoint, which
    proxies to the raft leader like every master HTTP verb."""
    import json as _json

    from seaweedfs_tpu.util import http_client
    p = argparse.ArgumentParser(prog="volume.lifecycle")
    g = p.add_mutually_exclusive_group()
    g.add_argument("-status", action="store_true",
                   help="print engine status (default)")
    g.add_argument("-pause", action="store_true",
                   help="hold the policy loop (no new transitions)")
    g.add_argument("-resume", action="store_true")
    g.add_argument("-force", action="store_true",
                   help="queue one forced transition now")
    p.add_argument("-volumeId", type=int, default=0,
                   help="volume for -force")
    p.add_argument("-target", default="",
                   help="target state for -force: hot | warm | cold")
    args = p.parse_args(argv)

    def call(method="GET", **params):
        q = "&".join(f"{k}={v}" for k, v in params.items())
        resp = http_client.request(
            method, f"{env.master_url}/cluster/lifecycle"
                    + (f"?{q}" if q else ""), timeout=30)
        body = _json.loads(resp.body)
        if body.get("error"):
            raise RuntimeError(body["error"])
        return body

    if args.pause:
        call("POST", action="pause")
        out.write("lifecycle paused\n")
        return
    if args.resume:
        call("POST", action="resume")
        out.write("lifecycle resumed\n")
        return
    if args.force:
        if not args.volumeId or not args.target:
            raise ValueError("-force needs -volumeId and -target")
        body = call("POST", action="force", volumeId=args.volumeId,
                    target=args.target)
        out.write(f"volume {args.volumeId}: {body['queued']} queued\n")
        return
    st = call()
    if not st.get("enabled"):
        out.write("lifecycle disabled (start the master with "
                  "-lifecycle)\n")
        return
    states = st.get("states", {})
    out.write(
        f"lifecycle: {'PAUSED' if st.get('paused') else 'running'}"
        f"{' (dry run)' if st.get('dry_run') else ''} "
        f"passes:{st.get('passes', 0)} "
        f"interval:{st.get('interval_s', 0):.0f}s\n"
        f"volumes: hot:{states.get('hot', 0)} "
        f"warm:{states.get('warm', 0)} cold:{states.get('cold', 0)}\n"
        f"transitions: ok:{st.get('transitions_ok', 0)} "
        f"err:{st.get('transitions_err', 0)} "
        f"queued:{st.get('queued_forced', 0)}\n")
    for d in st.get("decisions", [])[-10:]:
        out.write(f"  vol {d['vid']}: {d['kind']} -> {d['target']} "
                  f"[{d['outcome']}] {d['reason']}\n")


@command("volume.tier.download", "bring a cloud-tiered volume's .dat (or "
                                 "EC shards) back to local disk")
def volume_tier_download(env: CommandEnv, argv: List[str], out) -> None:
    """Reference: weed/shell/command_volume_tier_download.go.

    Idempotent over holders, mirroring volume.tier.upload: a holder
    whose copy is already local is SKIPPED instead of aborting the
    remaining-holder loop — a retry after a partial download failure
    (the lifecycle engine re-runs the same command after backoff)
    finishes the stragglers instead of wedging on the ones done."""
    import grpc as _grpc
    p = argparse.ArgumentParser(prog="volume.tier.download")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-keepRemoteDatFile", action="store_true")
    args = p.parse_args(argv)
    for url in env.lookup(args.volumeId):
        try:
            for resp in env.volume_server(url).VolumeTierMoveDatFromRemote(
                    volume_server_pb2.VolumeTierMoveDatFromRemoteRequest(
                        volume_id=args.volumeId,
                        keep_remote_dat_file=args.keepRemoteDatFile)):
                out.write(f"volume {args.volumeId} on {url}: "
                          f"{resp.processed} bytes restored\n")
        except _grpc.RpcError as e:
            if "not cloud-tiered" in (e.details() or ""):
                out.write(f"volume {args.volumeId} on {url}: "
                          f"already local, skipped\n")
                continue
            raise
