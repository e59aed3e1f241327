"""Traffic: a closed loop of one operator scrubbing a volume server's EC
volumes, with one latent sector error a pass.

Set-up is ``ec_repair``'s — the loaded volumes encoded (the mix's
``prepare_command``), each sealed ``.dat`` kept under a second name —
and then ONE untimed pass with one planted sector: the compare-and-count
program, the rebuild of one shard and the staging buffers of both widths
then exist before the window (``run.py``'s ``warm_up`` knows the GF maps
alone). A round of the window is the damage — one sector of one shard
file of one volume, every byte of it replaced by another value, written
through the file's path under the mounted shard — and then ONE shell
command (the mix's ``command``, which returns when the pass has ended
and names every volume it covered with its verdict); it is done when
the master shows all 14 shards of every volume again. Rounds run back
to back for ``--seconds``; the round in flight is finished and counted.
The damage is inside the window, and its seconds are in the notes. A
job is one volume scrubbed in one round: the rate is the ``.dat`` bytes
of every volume, in every round whose report was what was planted, over
all the time to the last finish.

``correct``: every planted sector — the untimed pass's too — re-read
after its round (and again after the window) against the bytes kept
before the damage; every round's report against what was planted, to
the letter: the damaged volume and shard named as rebuilt, every other
volume named clean, nothing unrecoverable, no healthy shard condemned;
and after the window every byte of all 14 shard files of every volume
against the plain reference's striping and parity of the kept ``.dat``,
whose needles are held against the payloads remade from
``(seed, index)``.
"""

from __future__ import annotations

import os
import re
import statistics
import time

import numpy as np

from benchmark import reference
from benchmark.cluster import check
from benchmark.drivers import ec_job, ec_repair

UNTIMED = 1 << 20                # the round number of set-up's pass


def prepare(run) -> dict:
    # the kept .dats, the needle table, every volume encoded and its 14
    # shards registered: the repair cell's set-up
    state = ec_repair.prepare(run)
    state["sectors"] = []                # every sector planted, in order
    state["untimed"] = _round(run, state, UNTIMED)
    run.say({"untimed_pass": state["untimed"]})
    return state


def _damage(run, state: dict, k: int) -> dict:
    """Round ``k``'s latent sector error: which sector, what it held."""
    bench, mix = run.bench, run.traffic
    sector = run.config["assumed"]["sector_bytes"]
    vid = bench.vids[k % len(bench.vids)]
    sid = mix["damaged_shards"][k % len(mix["damaged_shards"])]
    path = bench.shard_paths(vid)[sid]
    rng = np.random.default_rng([run.seed, k, sector])
    offset = int(rng.integers(0, os.path.getsize(path) // sector)) * sector
    with open(path, "r+b") as f:
        f.seek(offset)
        before = f.read(sector)
        check(len(before) == sector, f"{path}: no whole sector at {offset}")
        # every byte another value: XOR with a non-zero byte
        after = bytes(np.frombuffer(before, dtype=np.uint8) ^
                      rng.integers(1, 256, sector, dtype=np.uint8))
        f.seek(offset)
        f.write(after)
    planted = {"round": k, "volume": vid, "shard": sid, "offset": offset,
               "path": path, "before": before, "unrepaired": False}
    state["sectors"].append(planted)
    return planted


def _sector_differs(planted: dict) -> bool:
    try:
        with open(planted["path"], "rb") as f:
            f.seek(planted["offset"])
            return f.read(len(planted["before"])) != planted["before"]
    except OSError:
        return True


def _misreported(run, out: str, planted: dict) -> str:
    """'' when the command's output is exactly what was planted, else
    what is wrong with it."""
    bench, mix = run.bench, run.traffic
    ledger = re.search(mix["ledger_line"], out)
    if ledger is None:
        return f"no ledger line: {out!r}"
    state, passes, found, repaired, unrecoverable = ledger.groups()
    if (state, int(passes), int(found), int(repaired), int(unrecoverable)) \
            != ("idle", 1, 1, 1, 0):
        return f"ledger {ledger.group(0)!r}"
    want = {vid: mix["verdict_clean"] for vid in bench.vids}
    want[planted["volume"]] = mix["verdict_rebuilt"].format(
        shard=planted["shard"])
    got = {int(vid): verdict
           for vid, verdict in re.findall(mix["verdict_line"], out)}
    if got != want:
        return f"verdicts {got}, planted {want}"
    return ""


def _round(run, state: dict, k: int) -> dict:
    bench, mix = run.bench, run.traffic
    t = time.perf_counter
    tb = t()
    planted = _damage(run, state, k)
    t_damaged = t()
    out = bench.shell.run_command(mix["command"])
    t_cmd = t()
    wrong = _misreported(run, out, planted)
    bench.wait_shards(bench.vids, mix["shards_when_done"])
    planted["unrepaired"] = _sector_differs(planted)
    if wrong:
        run.note(f"round {k}: misreported: {wrong}")
    if planted["unrepaired"]:
        run.note(f"round {k}: the sector at {planted['offset']} of "
                 f"{planted['path']} was not repaired")
    return {"seconds": t() - tb, "damage_s": t_damaged - tb,
            "command_s": t_cmd - t_damaged, "volume": planted["volume"],
            "shard": planted["shard"], "offset": planted["offset"],
            "misreported": wrong}


def window(run, state: dict) -> dict:
    bench, mix = run.bench, run.traffic
    vids = list(bench.vids)
    round_bytes = sum(bench.dat_sizes[v] for v in vids)
    traced_rounds = mix.get("trace_batches", 1)
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
    # what the device did in a traced round, as .dat bytes of a [4, 10]
    # map (layer_metrics/rs_verify_roofline.json): the pass over the
    # pool, the re-verify of the repaired volume, and its [1, 10]
    # rebuild, which moves 11 bytes where a verify moves 14
    traced = rounds[:traced_rounds]
    equivalent = sum(round_bytes + (1 + 11 / 14) * bench.dat_sizes[r["volume"]]
                     for r in traced)
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
                  "damage_seconds": sum(r["damage_s"] for r in rounds)},
    }


def _missed_sector(state: dict) -> None:
    """The control: one round's damage written again, as a scrub that
    missed it would have left it."""
    planted = state["sectors"][-1]
    with open(planted["path"], "r+b") as f:
        f.seek(planted["offset"])
        f.write(bytes(b ^ 0x5A for b in planted["before"]))


CONTROLS = {"missed-sector": _missed_sector}


def verify(run, state: dict, result: dict) -> dict:
    bench, code = run.bench, run.config["code"]
    large, small = code["large_block_bytes"], code["small_block_bytes"]
    threads = run.traffic.get("compare_threads", 4)
    if run.control:
        CONTROLS[run.control](state)
    t0 = time.perf_counter()
    unrepaired = sum(p["unrepaired"] or _sector_differs(p)
                     for p in state["sectors"])
    misreported = sum(bool(r["misreported"])
                      for r in [state["untimed"]] + result["notes"]["rounds"])
    differing = compared = missing = dat_wrong = dat_needles = 0
    for vid in bench.vids:
        dat_wrong += ec_job._needles_differing(
            run, state["kept"][vid], state["needles"][vid], threads)
        dat_needles += len(state["needles"][vid])
        paths = bench.shard_paths(vid)
        missing += sum(not os.path.exists(p) for p in paths)
        got = reference.compare_volume(state["kept"][vid], paths, large,
                                       small, threads=threads)
        differing += got["differing"]
        compared += got["compared"]
    run.say({"verify": {"seconds": time.perf_counter() - t0,
                        "sectors_planted": len(state["sectors"]),
                        "bytes_compared": compared,
                        "dat_needles_compared": dat_needles,
                        "volumes": len(bench.vids)}})
    return {"shard_bytes_differing": {"value": differing, "limit": 0},
            "shard_files_missing": {"value": missing, "limit": 0},
            "dat_needles_differing": {"value": dat_wrong, "limit": 0},
            "sectors_unrepaired": {"value": unrepaired, "limit": 0},
            "damage_misreported": {"value": misreported, "limit": 0}}
