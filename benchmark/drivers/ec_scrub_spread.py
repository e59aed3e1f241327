"""Traffic: ``ec_scrub``'s closed loop of one operator, on the layout a
multi-server cluster has: each volume's 14 shards spread over four
volume servers.

Set-up loads the volumes on the one server the cluster starts with
(``benchmark/cluster.py`` finds them there), then joins the
configuration's ``join_volume_servers`` more, each with the cluster's own
arguments, keeps each ``.dat`` as ``ec_job`` does and encodes the
volumes one ``prepare_command`` each: ``ec.encode`` spreads a volume's
shards over the servers by free slots, which the volumes still sealed
on the first server lower there, so each waits until the master has
seen the one before it go, and the layout is the same in every run.
The first server is then left 1-3 shards of a volume, so each volume's
shards are evened out as ``ec.balance`` keeps them spread: a shard at a
time from the server that holds the most of the volume to the one that
holds the fewest, until every server holds 3 or 4 of its 14. Where each
shard lies then is the layout every later round is held to.
One untimed pass with one planted sector follows, as in ``ec_scrub``; a
program whose report of it is not what was planted ends the run there.

A round is ``ec_scrub``'s: one sector of one shard file, in that shard's
file on the server that holds it, then ONE shell command, which goes to
every server; it is done when the master shows all 14 shards of every
volume on the servers that held them before. The command prints one
ledger line a server and one verdict line a volume, on the line of the
server that verified it.

``correct``: ``ec_scrub``'s comparison, each with limit 0, over the files
where they lie — and three more, each with limit 0, summed over the
untimed round and the window's: ``needles_unchecked`` (|the needles on
the four ledger lines - the live needles of the pool|), ``stripes_unverified``
(volumes no server's report named) and ``layout_moved`` (shards not on
the server that held them before the round).
"""

from __future__ import annotations

import copy
import inspect
import io
import pathlib
import re
import statistics
import time

from benchmark.cluster import COLLECTION, BenchFailure, check
from benchmark.drivers import ec_job, ec_scrub

DATA_SHARDS = 10


def _join(run) -> None:
    """The configuration's further volume servers, started as
    ``tests/cluster_util.Cluster`` starts its own and added to it (its
    ``stop`` stops them)."""
    from seaweedfs_tpu.server.volume import VolumeServer
    from tests.cluster_util import Cluster, free_port_pair
    bench = run.bench
    cluster = bench.cluster
    given = dict(bench.cluster_args)
    defaults = inspect.signature(Cluster.__init__).parameters

    def arg(name):
        return given.get(name, defaults[name].default)

    first = len(cluster.volume_servers)
    last = first + run.config["join_volume_servers"]
    for i in range(first, last):
        d = pathlib.Path(bench.data_dir) / f"vol{i}"
        d.mkdir(parents=True, exist_ok=True)
        racks = arg("racks")
        vs = VolumeServer(
            master_url=cluster.master.url, directories=[str(d)],
            port=free_port_pair(),
            max_volume_counts=[arg("volumes_per_server")],
            pulse_seconds=arg("pulse_seconds"), ec_encoder=arg("ec_encoder"),
            rack=racks[i] if racks else "", **(arg("volume_kwargs") or {}))
        vs.start()
        cluster.volume_servers.append(vs)
    cluster.wait_for_nodes(last)


def _layout(bench, vid: int) -> dict:
    """{shard id: [holder urls]} as the master shows it."""
    out: dict = {}
    for url, bits in bench.cluster.master.topo.lookup_ec(vid).items():
        for sid in bits.shard_ids:
            out.setdefault(sid, []).append(url)
    return {sid: sorted(urls) for sid, urls in sorted(out.items())}


def _even_out(bench, vid: int) -> dict:
    """Volume ``vid``'s shards moved, one at a time, from the server
    that holds the most of them to the one that holds the fewest (the
    earlier server of a tie), the lowest shard id of the first, until
    no two servers' counts differ by more than one; the layout then."""
    from seaweedfs_tpu.shell.command_ec import apply_shard_move
    from seaweedfs_tpu.shell.ec_common import ShardMove
    servers = [vs.url for vs in bench.cluster.volume_servers]
    held = {url: [] for url in servers}
    for sid, urls in _layout(bench, vid).items():
        check(len(urls) == 1, f"volume {vid}: shard {sid} on {urls}")
        held[urls[0]].append(sid)
    while True:
        src = max(servers, key=lambda u: len(held[u]))
        dst = min(servers, key=lambda u: len(held[u]))
        if len(held[src]) - len(held[dst]) <= 1:
            break
        sid = min(held[src])
        apply_shard_move(bench.shell.env, ShardMove(vid, (sid,), src, dst),
                         COLLECTION, io.StringIO())
        held[src].remove(sid)
        held[dst].append(sid)
    want = {sid: [url] for url, sids in held.items() for sid in sids}
    bench.cluster.wait_for(lambda: _layout(bench, vid) == dict(sorted(
        want.items())), timeout=60.0, what=f"volume {vid} evened out")
    return _layout(bench, vid)


def _files(bench, vid: int) -> list:
    """The file of each shard of ``vid``, on the server that holds it."""
    paths = {}
    for vs in bench.cluster.volume_servers:
        ecv = vs.store.find_ec_volume(vid)
        for sid in (ecv.shards if ecv is not None else ()):
            paths[sid] = ecv.shards[sid].path
    check(sorted(paths) == list(range(14)),
          f"volume {vid}: shards {sorted(paths)} mounted")
    return [paths[sid] for sid in range(14)]


def prepare(run) -> dict:
    bench, mix = run.bench, run.traffic
    _join(run)
    state = ec_job.prepare(run)          # the kept .dats, the needle table
    topo = bench.cluster.master.topo
    for vid in bench.vids:
        out = bench.shell.run_command(
            mix["prepare_command"].format(volume_ids=vid))
        check(mix["prepare_done_marker"].format(vid=vid) in out,
              f"{mix['prepare_command']}: {out!r}")
        bench.cluster.wait_for(lambda vid=vid: not topo.lookup(vid,
                                                               COLLECTION),
                               timeout=60.0,
                               what=f"volume {vid} gone at the master")
    bench.wait_shards(bench.vids, mix["shards_when_done"])
    state["layout"] = {vid: _even_out(bench, vid) for vid in bench.vids}
    servers = len(bench.cluster.volume_servers)
    for vid, lay in state["layout"].items():
        counts = sorted(sum(url in urls for urls in lay.values())
                        for url in {u for us in lay.values() for u in us})
        even = [len(lay) // servers] * (servers - len(lay) % servers) + \
            [-(-len(lay) // servers)] * (len(lay) % servers)
        check(counts == even, f"volume {vid}: {counts} shards a server, "
                              f"not {even}")
    files = {vid: _files(bench, vid) for vid in bench.vids}
    # Bench.shard_paths names server 0's files; here each volume's lie
    # on four servers, and ec_scrub's damage and comparison take them
    # from where they lie
    bench.shard_paths = files.__getitem__
    state["live"] = sum(len(n) for n in state["needles"].values())
    state["sectors"] = []
    state["untimed"] = _round(run, state, ec_scrub.UNTIMED)
    run.say({"untimed_pass": state["untimed"],
             "layout": {vid: {url: [s for s, us in lay.items() if url in us]
                              for url in sorted({u for us in lay.values()
                                                 for u in us})}
                        for vid, lay in state["layout"].items()}})
    if state["untimed"]["misreported"]:
        raise BenchFailure("the untimed pass did not report what was "
                           f"planted: {state['untimed']['misreported']}")
    return state


def _report(run, out: str) -> tuple:
    """The command's ledger lines ({url: (state, passes, needles, found,
    repaired, unrecoverable)}) and verdict lines ([(url, vid, verdict)])."""
    mix = run.traffic
    ledgers = {m[0]: (m[1],) + tuple(int(x) for x in m[2:])
               for m in re.findall(mix["ledger_line"], out, re.M)}
    named = [(url, int(vid), verdict)
             for url, vid, verdict in re.findall(mix["verdict_line"], out,
                                                 re.M)]
    return ledgers, named


def _misreported(run, ledgers: dict, named: list, planted: dict) -> str:
    """'' when the four servers' report is exactly what was planted,
    else what is wrong with it."""
    bench, mix = run.bench, run.traffic
    servers = sorted(vs.url for vs in bench.cluster.volume_servers)
    if sorted(ledgers) != servers:
        return f"ledger lines of {sorted(ledgers)}, servers {servers}"
    if any(led[:2] != ("idle", 1) for led in ledgers.values()):
        return f"ledgers {ledgers}"
    found, repaired, unrecoverable = (sum(led[i] for led in ledgers.values())
                                      for i in (3, 4, 5))
    if (found, repaired, unrecoverable) != (1, 1, 0):
        return f"found {found} repaired {repaired} unrecoverable " \
               f"{unrecoverable}: {ledgers}"
    want = {vid: mix["verdict_clean"] for vid in bench.vids}
    want[planted["volume"]] = mix["verdict_rebuilt"].format(
        shard=planted["shard"])
    got = sorted((vid, verdict) for _, vid, verdict in named)
    if got != sorted(want.items()):
        return f"verdicts {named}, planted {want}"
    return ""


def _round(run, state: dict, k: int) -> dict:
    bench, mix = run.bench, run.traffic
    t = time.perf_counter
    tb = t()
    planted = ec_scrub._damage(run, state, k)
    t_damaged = t()
    out = bench.shell.run_command(mix["command"])
    t_cmd = t()
    ledgers, named = _report(run, out)
    wrong = _misreported(run, ledgers, named, planted)
    bench.wait_shards(bench.vids, mix["shards_when_done"])
    planted["unrepaired"] = ec_scrub._sector_differs(planted)
    moved = sum(_layout(bench, vid).get(sid) != urls
                for vid, lay in state["layout"].items()
                for sid, urls in lay.items())
    verifier = [url for url, vid, _ in named if vid == planted["volume"]]
    holders = state["layout"][planted["volume"]][planted["shard"]]
    needles = sum(led[2] for led in ledgers.values())
    if wrong:
        run.note(f"round {k}: misreported: {wrong}")
    if planted["unrepaired"]:
        run.note(f"round {k}: the sector at {planted['offset']} of "
                 f"{planted['path']} was not repaired")
    return {"seconds": t() - tb, "damage_s": t_damaged - tb,
            "command_s": t_cmd - t_damaged, "volume": planted["volume"],
            "shard": planted["shard"], "offset": planted["offset"],
            "verified_by": {url: sorted(v for u, v, _ in named if u == url)
                            for url in sorted({u for u, _, _ in named})},
            "damaged_on": "verifier" if verifier and verifier[0] in holders
            else "remote",
            "needles": needles,
            "needles_unchecked": abs(needles - state["live"]),
            "stripes_unverified": len(set(bench.vids)
                                      - {vid for _, vid, _ in named}),
            "layout_moved": moved, "misreported": wrong}


def window(run, state: dict) -> dict:
    bench = run.bench
    vids = list(bench.vids)
    round_bytes = sum(bench.dat_sizes[v] for v in vids)
    traced_rounds = run.traffic.get("trace_batches", 1)
    t = time.perf_counter
    rounds = []
    t0 = t()
    t_last = t0
    run.tracer.start()
    while not rounds or t() - t0 < run.seconds:
        rounds.append(_round(run, state, len(rounds)))
        t_last = t()
        if len(rounds) == traced_rounds:
            run.tracer.stop()
    run.tracer.stop()
    good = [r for r in rounds if not r["misreported"]]
    done_bytes = round_bytes * len(good)
    # as ec_scrub reckons it: the pass over the pool, the re-verify of
    # the repaired volume and its [1, 10] rebuild (11 bytes moved where
    # a verify moves 14), as .dat bytes of a [4, 10] map
    traced = rounds[:traced_rounds]
    equivalent = sum(round_bytes + (1 + 11 / 14) * bench.dat_sizes[r["volume"]]
                     for r in traced)
    # what a round costs by where its sector lay: on the volume's
    # verifier or another holder, in a data or a parity shard (the mix
    # repeats every len(damaged_shards) rounds, more than a window holds)
    by_site: dict = {}
    for r in rounds:
        kind = "data" if r["shard"] < DATA_SHARDS else "parity"
        by_site.setdefault(f"{r['damaged_on']}.{kind}", []).append(
            r["seconds"])
    return {
        "attempted": len(vids) * len(rounds),
        "failed": len(vids) * (len(rounds) - len(good)),
        "end_to_end": {"ec_job_mbps": done_bytes / 1e6 / (t_last - t0)},
        "work": {"gib_done": done_bytes / (1 << 30),
                 "traced": {"dat_bytes": round_bytes * len(traced),
                            "verify_equivalent_dat_bytes": equivalent}},
        "notes": {"rounds": rounds,
                  "scrub_round_s": statistics.median(
                      r["seconds"] for r in rounds),
                  "round_s_by_site": {site: statistics.median(s)
                                      for site, s in sorted(by_site.items())},
                  "damage_seconds": sum(r["damage_s"] for r in rounds),
                  "damaged_on_verifier": sum(r["damaged_on"] == "verifier"
                                             for r in rounds)},
    }


def _deferred_lost(run, state: dict, result: dict) -> None:
    """The control: every round as a pass in which the volumes a server
    defers are never verified by anyone — what a scrub that skips every
    volume without all its data shards local reports on this layout:
    no volume named, no needle counted, no damage found, so every
    planted sector is left as it was damaged."""
    for r in [state["untimed"]] + result["notes"]["rounds"]:
        r.update(needles_unchecked=state["live"],
                 stripes_unverified=len(run.bench.vids),
                 misreported="deferred-lost: nothing verified")
    for planted in state["sectors"]:
        with open(planted["path"], "r+b") as f:
            f.seek(planted["offset"])
            f.write(bytes(b ^ 0x5A for b in planted["before"]))


CONTROLS = {"deferred-lost": _deferred_lost}


def verify(run, state: dict, result: dict) -> dict:
    if run.control:
        CONTROLS[run.control](run, state, result)
    # ec_scrub's comparison over the files where they lie (its own
    # controls are not this cell's)
    bare = copy.copy(run)
    bare.control = ""
    compared = ec_scrub.verify(bare, state, result)
    rounds = [state["untimed"]] + result["notes"]["rounds"]
    for name in ("needles_unchecked", "stripes_unverified", "layout_moved"):
        compared[name] = {"value": sum(r[name] for r in rounds), "limit": 0}
    return compared
