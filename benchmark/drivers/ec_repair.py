"""Traffic: a closed loop of one operator repairing a server loss.

Set-up encodes the loaded volumes (the mix's ``prepare_command``) and
keeps each sealed ``.dat`` under a second name, as ``ec_job`` does. A
round of the window is the loss — the configuration's ``lost_shards`` of
EVERY volume, unmounted and deleted through the volume server's gRPC —
then ONE shell command (the mix's ``command``), and it is done when the
command has named every volume as rebuilt and the master shows all its
shards again. Rounds run back to back for ``--seconds``; the round in
flight is finished and counted. The loss is inside the window, and its
seconds are in the notes. The rate is all the logical volume bytes
repaired, in all rounds, over all the time to the last finish.

Before the next loss deletes them, the files a round rebuilt get a
second name (a hard link under ``kept/round-<k>/``), so that the
comparison holds EVERY round to the guarantee and not the last alone.

``correct``: every byte of every shard file the timed commands rebuilt,
in every round, and of the 12 survivors after the window, against the
plain reference's striping and parity of the kept ``.dat``; and the
data of every needle of that ``.dat`` against the payload remade from
``(seed, index)``. One pass of the reference a volume serves all rounds.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import reference
from benchmark.cluster import check
from benchmark.drivers import ec_job


def prepare(run) -> dict:
    bench, mix = run.bench, run.traffic
    state = ec_job.prepare(run)          # the kept .dats, the needle table
    out = bench.shell.run_command(mix["prepare_command"].format(
        volume_ids=",".join(str(v) for v in bench.vids)))
    for vid in bench.vids:
        check(mix["prepare_done_marker"].format(vid=vid) in out,
              f"{mix['prepare_command']}: {out!r}")
    bench.wait_shards(bench.vids, mix["shards_when_done"])
    state["rounds"] = []                 # round -> {vid: {sid: kept path}}
    return state


def _keep_rebuilt(bench, k: int, lost) -> dict:
    """A second name for every file round ``k`` rebuilt: the next loss
    deletes the first."""
    keep = os.path.join(bench.data_dir, "kept", f"round-{k}")
    os.makedirs(keep)
    kept = {}
    for vid in bench.vids:
        paths = bench.shard_paths(vid)
        kept[vid] = {}
        for sid in lost:
            dst = os.path.join(keep, os.path.basename(paths[sid]))
            os.link(paths[sid], dst)
            kept[vid][sid] = dst
    return kept


def window(run, state: dict) -> dict:
    bench, mix = run.bench, run.traffic
    lost = sorted(run.config["lost_shards"])
    vids = list(bench.vids)
    round_bytes = sum(bench.dat_sizes[v] for v in vids)
    traced_rounds = mix.get("trace_batches", 1)
    t = time.perf_counter
    rounds = []
    t0 = t()
    t_last = t0
    run.tracer.start()
    while not rounds or t() - t0 < run.seconds:
        tb = t()
        bench.degrade(lost)              # the loss; the master shows it
        t_lost = t()
        out = bench.shell.run_command(mix["command"])
        t_cmd = t()
        for vid in vids:
            check(mix["done_marker"].format(vid=vid, lost=lost) in out,
                  f"{mix['command']}: {out!r}")
        bench.wait_shards(vids, mix["shards_when_done"])
        t_last = t()
        rounds.append({"seconds": t_last - tb, "loss_s": t_lost - tb,
                       "command_s": t_cmd - t_lost,
                       "mbps": round_bytes / 1e6 / (t_last - tb)})
        state["rounds"].append(_keep_rebuilt(bench, len(rounds) - 1, lost))
        if len(rounds) == traced_rounds:
            run.tracer.stop()
    run.tracer.stop()
    done_bytes = round_bytes * len(rounds)
    traced_rounds = min(len(rounds), traced_rounds)
    return {
        "attempted": len(vids) * len(rounds),
        "failed": 0,                     # a round that fails ends the run
        "end_to_end": {"ec_job_mbps": done_bytes / 1e6 / (t_last - t0)},
        "work": {"gib_done": done_bytes / (1 << 30),
                 "traced": {"dat_bytes": round_bytes * traced_rounds}},
        "notes": {"rounds": rounds, "lost_shards": lost,
                  "loss_seconds": sum(r["loss_s"] for r in rounds)},
    }


def _weak_decode(lost):
    """The control: the lost shards decoded from the 10 first survivors
    with one coefficient of the decode matrix changed by one bit.
    Returns (survivors, matrix)."""
    present = [s for s in range(reference.TOTAL_SHARDS) if s not in lost]
    m = reference.decode_matrix(present, lost).copy()
    m[-1, 4] ^= 1
    return present[:reference.DATA_SHARDS], m


CONTROLS = {"weak-decode": _weak_decode}


def _write_decoded(paths, targets, survivors, matrix, piece: int = 1 << 20):
    """``targets`` = [{sid: path}]: each lost shard of one volume written
    from ``matrix`` applied to the surviving files, to every round's
    name for it."""
    ins = [open(paths[s], "rb") for s in survivors]
    outs = [{sid: open(p, "r+b") for sid, p in t.items()} for t in targets]
    try:
        size = os.path.getsize(paths[survivors[0]])
        for off in range(0, size, piece):
            n = min(piece, size - off)
            rows = []
            for f in ins:
                f.seek(off)
                rows.append(np.frombuffer(f.read(n), dtype=np.uint8))
            got = reference.apply(matrix, np.stack(rows))
            for files in outs:
                for row, sid in enumerate(sorted(files)):
                    files[sid].seek(off)
                    files[sid].write(got[row].tobytes())
    finally:
        for f in ins + [f for files in outs for f in files.values()]:
            f.close()


def _differing(dat_path: str, file_sets, rows) -> int:
    """Bytes of ``file_sets`` = [{sid: path}] that differ over ``rows``
    from what the reference says shard ``sid`` holds there; a short
    file differs where it ends."""
    files = [(sid, open(p, "rb")) for fs in file_sets
             for sid, p in fs.items()]
    differing = 0
    try:
        for off, want in reference._spans(dat_path, rows,
                                          reference.parity_matrix()):
            n = want.shape[1]
            for sid, f in files:
                f.seek(off)
                got = np.frombuffer(f.read(n), dtype=np.uint8)
                differing += n - got.size + int(np.count_nonzero(
                    got != want[sid, :got.size]))
    finally:
        for _, f in files:
            f.close()
    return differing


def _compare_volume(dat_path: str, file_sets, large: int, small: int,
                    threads: int) -> dict:
    """``reference.compare_volume`` for several sets of files of one
    volume at once (all 14 in place, and each earlier round's rebuilt
    ones): one computation of the reference serves them all."""
    dat_size = os.path.getsize(dat_path)
    shard_size = reference.layout(dat_size, large, small)[2]
    differing = missing = compared = 0
    there = []
    for fs in file_sets:
        there.append({})
        for sid, p in fs.items():
            compared += shard_size
            if not os.path.exists(p):
                missing += 1
                differing += shard_size
                continue
            differing += max(0, os.path.getsize(p) - shard_size)  # surplus
            there[-1][sid] = p
    rows = list(reference._rows(dat_size, large, small))
    threads = max(1, min(threads, len(rows)))
    with ThreadPoolExecutor(threads) as pool:
        differing += sum(pool.map(
            lambda part: _differing(dat_path, there, part),
            [rows[i::threads] for i in range(threads)]))
    return {"differing": differing, "missing": missing, "compared": compared}


def verify(run, state: dict, result: dict) -> dict:
    bench, code = run.bench, run.config["code"]
    large, small = code["large_block_bytes"], code["small_block_bytes"]
    threads = run.traffic.get("compare_threads", 4)
    lost = result["notes"]["lost_shards"]
    rounds = state["rounds"]
    if run.control:
        survivors, matrix = CONTROLS[run.control](lost)
    t0 = time.perf_counter()
    differing = compared = missing = dat_wrong = dat_needles = 0
    for vid in bench.vids:
        paths = bench.shard_paths(vid)
        # the last round's rebuilt files are the ones in place
        earlier = [r[vid] for r in rounds[:-1]]
        if run.control:
            # the reference, broken, in the program's place in every round
            _write_decoded(paths, earlier + [{s: paths[s] for s in lost}],
                           survivors, matrix)
        dat_wrong += ec_job._needles_differing(
            run, state["kept"][vid], state["needles"][vid], threads)
        dat_needles += len(state["needles"][vid])
        got = _compare_volume(state["kept"][vid],
                              [dict(enumerate(paths))] + earlier,
                              large, small, threads)
        differing += got["differing"]
        missing += got["missing"]
        compared += got["compared"]
    run.say({"verify": {"seconds": time.perf_counter() - t0,
                        "rounds_compared": len(rounds),
                        "bytes_compared": compared,
                        "dat_needles_compared": dat_needles,
                        "volumes": len(bench.vids)}})
    return {"shard_bytes_differing": {"value": differing, "limit": 0},
            "shard_files_missing": {"value": missing, "limit": 0},
            "dat_needles_differing": {"value": dat_wrong, "limit": 0}}
