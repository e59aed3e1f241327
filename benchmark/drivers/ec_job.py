"""Traffic: a closed loop of one operator sending background EC jobs.

The mix's file gives the shell command as a template (``{volume_ids}``
is the batch, comma-separated), how many volumes go into one command,
the text that says a volume is done and how many shards the master has
to show for it then. The loop sends a command, waits until every volume
of the batch is done and registered, and sends the next — for as long
as less than ``--seconds`` has passed and the pool has volumes. The
batch in flight is finished and counted: the rate is all the logical
volume bytes whose job finished over all the time to the last finish.

``correct``: every byte of every shard file the timed commands wrote,
against the plain reference's encode of the same sealed ``.dat``
(kept under a second name in set-up, since ``ec.encode`` deletes the
volume). The ``.dat`` is the job's input and the program stored it, so
the data of every needle in it is held against the payload remade from
``(seed, index)``: the reference's input is tied to the seed too.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

from benchmark import reference
from benchmark.cluster import check, payload


def prepare(run) -> dict:
    bench = run.bench
    kept = {vid: bench.keep_dat(vid) for vid in bench.vids}
    # where each needle lies in its volume's .dat, read from the .idx
    # now: the job deletes it with the volume
    needles = {}
    for vid in bench.vids:
        recs = reference.needle_records(bench.bases[vid] + ".idx",
                                        bench.dat_sizes[vid])
        needles[vid] = [(index, recs[reference.fid_key(fid)][0])
                        for fid, index in bench.fids[vid]]
    return {"kept": kept, "pool": list(bench.vids), "needles": needles}


def window(run, state: dict) -> dict:
    bench, mix = run.bench, run.traffic
    pool = state["pool"]
    n = mix["batch_volumes"]
    t = time.perf_counter
    done, batches = [], []
    attempted = 0
    t0 = t()
    t_last = t0
    run.tracer.start()
    while len(pool) >= n and t() - t0 < run.seconds:
        batch, pool[:] = pool[:n], pool[n:]
        attempted += len(batch)
        tb = t()
        out = bench.shell.run_command(mix["command"].format(
            volume_ids=",".join(str(v) for v in batch)))
        for vid in batch:
            check(mix["done_marker"].format(vid=vid) in out,
                  f"{mix['command']}: {out!r}")
        bench.wait_shards(batch, mix["shards_when_done"])
        t_last = t()
        done += batch
        nbytes = sum(bench.dat_sizes[v] for v in batch)
        batches.append({"volumes": batch, "seconds": t_last - tb,
                        "mbps": nbytes / 1e6 / (t_last - tb)})
        if len(batches) == mix.get("trace_batches", 1):
            run.tracer.stop()
            state["traced_bytes"] = sum(bench.dat_sizes[v] for v in done)
    run.tracer.stop()
    check(done, "no job finished in the window")
    done_bytes = sum(bench.dat_sizes[v] for v in done)
    notes = {"batches": batches,
             "pool_ran_dry": not pool and t() - t0 < run.seconds}
    if notes["pool_ran_dry"]:
        run.note(f"the pool ran dry after {t_last - t0:.1f} s of "
                 f"{run.seconds} s: the window ends there")
    state["done"] = done
    return {
        "attempted": attempted,
        "failed": attempted - len(done),
        "end_to_end": {"ec_job_mbps": done_bytes / 1e6 / (t_last - t0)},
        "work": {"gib_done": done_bytes / (1 << 30),
                 "traced": {"dat_bytes": state.get("traced_bytes",
                                                   done_bytes)}},
        "notes": notes,
    }


def _needles_differing(run, dat_path: str, needles, threads: int) -> int:
    """How many of a kept ``.dat``'s needles do not hold the payload of
    their ``(seed, index)``."""
    nbytes = run.bench.needle_bytes

    def part(k: int) -> int:
        wrong = 0
        with open(dat_path, "rb") as f:
            for index, off in needles[k::threads]:
                f.seek(off + reference.DATA_OFFSET_IN_RECORD)
                wrong += f.read(nbytes) != payload(run.seed, index, nbytes)
        return wrong

    with ThreadPoolExecutor(threads) as pool:
        return sum(pool.map(part, range(threads)))


def _weak_parity():
    """The control: RS(10,4) with one parity coefficient changed — the
    shard files are all there and all the right size, and a loss that
    needs that row no longer decodes."""
    m = reference.parity_matrix().copy()
    m[3, 9] ^= 1
    return m


CONTROLS = {"weak-parity": _weak_parity}


def verify(run, state: dict, result: dict) -> dict:
    bench, code = run.bench, run.config["code"]
    large, small = code["large_block_bytes"], code["small_block_bytes"]
    threads = run.traffic.get("compare_threads", 4)
    if run.control:
        # the reference, broken, in the program's place
        matrix = CONTROLS[run.control]()
        for vid in state["done"]:
            reference.write_shards(state["kept"][vid], bench.shard_paths(vid),
                                   large, small, matrix)
    t0 = time.perf_counter()
    differing = compared = missing = dat_wrong = dat_needles = 0
    for vid in state["done"]:
        dat_wrong += _needles_differing(run, state["kept"][vid],
                                        state["needles"][vid], threads)
        dat_needles += len(state["needles"][vid])
        paths = bench.shard_paths(vid)
        missing += sum(not os.path.exists(p) for p in paths)
        got = reference.compare_volume(state["kept"][vid], paths, large,
                                       small, threads=threads)
        differing += got["differing"]
        compared += got["compared"]
    run.say({"verify": {"seconds": time.perf_counter() - t0,
                        "bytes_compared": compared,
                        "dat_needles_compared": dat_needles,
                        "volumes": len(state["done"])}})
    return {"shard_bytes_differing": {"value": differing, "limit": 0},
            "shard_files_missing": {"value": missing, "limit": 0},
            "dat_needles_differing": {"value": dat_wrong, "limit": 0}}
