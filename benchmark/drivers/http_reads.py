"""Traffic: closed-loop GETs of needles by fid, straight at the volume
server.

The mix's file gives the number of clients, the shell command that
set-up runs over the loaded volumes before any read (``ec.encode``; the
configuration then says which shards are lost), and which needles are
eligible, by the bytes of a needle's record that lie on a lost shard:
``min_lost_bytes`` 1 takes those whose read has to reconstruct. Which
pieces a needle has is worked out with the benchmark's own copy of the
striping arithmetic (``reference.locate``) from the volume's ``.idx``
(``reference.needle_records``).

The clients run in a child process that never imports jax
(``http_reads_client.py``); locations are resolved once in set-up, so a
latency is the GET alone.

``correct``: every GET of the window answered, and with exactly the
bytes stored under that fid — the payload remade from (seed, index).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from benchmark import reference
from benchmark.cluster import check, payload
from benchmark.drivers.http_reads_client import digest

HERE = os.path.dirname(os.path.abspath(__file__))


def prepare(run) -> dict:
    bench, mix, code = run.bench, run.traffic, run.config["code"]
    lost = run.config["lost_shards"]
    large, small = code["large_block_bytes"], code["small_block_bytes"]
    least = mix["min_lost_bytes"]
    needles, lost_bytes = [], []
    for vid in bench.vids:
        recs = reference.needle_records(bench.bases[vid] + ".idx",
                                        bench.dat_sizes[vid])
        for fid, index in bench.fids[vid]:
            off, length = recs[reference.fid_key(fid)]
            pieces = reference.locate(bench.dat_sizes[vid], large, small,
                                      off, length)
            on_lost = reference.bytes_on(pieces, lost)
            if on_lost >= least:
                needles.append((fid, index))
                lost_bytes.append((on_lost, pieces))
    check(needles, f"no needle has {least} bytes or more on a lost shard")
    if mix.get("prepare_command"):
        out = bench.shell.run_command(mix["prepare_command"].format(
            volume_ids=",".join(str(v) for v in bench.vids)))
        for vid in bench.vids:
            check(mix["prepare_done_marker"].format(vid=vid) in out,
                  f"{mix['prepare_command']}: {out!r}")
        bench.wait_shards(bench.vids, 14)
    if lost:
        bench.degrade(lost)
    host, port = bench.volume_server.url.split(":")
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "http_reads_client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    run.children.append(child)
    child.stdin.write(json.dumps({
        "seed": run.seed, "needle_bytes": bench.needle_bytes,
        "clients": mix["clients"], "host": host, "port": int(port),
        "needles": needles}) + "\n")
    child.stdin.flush()
    ready = child.stdout.readline().strip()
    check(ready == "ready", f"the client process said {ready!r}")
    run.say({"eligible_needles": len(needles),
             "of": sum(len(v) for v in bench.fids.values()),
             "mean_lost_bytes": sum(b for b, _ in lost_bytes)
             / len(needles)})
    return {"child": child, "needles": needles, "lost_bytes": lost_bytes}


def window(run, state: dict) -> dict:
    child, mix = state["child"], run.traffic
    run.tracer.start()
    run.tracer.stop_after(mix.get("trace_seconds", 5))
    try:
        child.stdin.write(f"go {run.seconds}\n")
        child.stdin.flush()
        line = child.stdout.readline()
        child.stdin.close()
        child.wait(timeout=60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    check(line.strip(), "the client process gave no result")
    got = json.loads(line)
    reads = got["reads"]
    check(reads, "no read was made in the window")
    t0 = min(r[0] for r in reads)
    t1 = max(r[1] for r in reads)
    right = [r for r in reads if r[3] == 0]
    # a failed or wrong read counts as slower than any
    worst = max(r[1] - r[0] for r in reads)
    lat = sorted((r[1] - r[0]) if r[3] == 0 else worst for r in reads)
    tr = run.tracer
    traced = [r for r in reads if tr.t0 is not None and tr.t1 is not None
              and tr.t0 <= r[0] and r[1] <= tr.t1]
    state["reads"] = reads
    return {
        "attempted": len(reads),
        "failed": len(reads) - len(right),
        "end_to_end": {"reads_per_s": len(right) / (t1 - t0),
                       "read_p95_ms": 1000.0 * _p95(lat)},
        "work": {"reads": len(reads),
                 "traced": {"lost_bytes": float(sum(
                     state["lost_bytes"][r[2]][0] for r in traced)),
                     "reads": len(traced)}},
        "notes": {"reads": len(reads), "window_s": t1 - t0,
                  "read_p50_ms": 1000.0 * lat[len(lat) // 2],
                  "read_max_ms": 1000.0 * worst,
                  # the load generator's own cost: CPU seconds of the
                  # client process per second of the window
                  "client_cpu_share": got["cpu_s"] / (t1 - t0)},
    }


def _p95(sorted_latencies) -> float:
    n = len(sorted_latencies)
    return sorted_latencies[min(n - 1, int(0.95 * n))]


def _no_reconstruct(run, state: dict, i: int) -> bytes:
    """The control's answer for needle ``i``: the bytes stored, with
    what lay on a lost shard left as zeros — a server that serves the
    surviving pieces and does not reconstruct."""
    _, index = state["needles"][i]
    body = bytearray(payload(run.seed, index, run.bench.needle_bytes))
    lost = set(run.config["lost_shards"])
    at = -reference.DATA_OFFSET_IN_RECORD
    for sid, _, n in state["lost_bytes"][i][1]:
        if sid in lost:
            a, b = max(at, 0), min(at + n, len(body))
            if b > a:
                body[a:b] = bytes(b - a)
        at += n
    return bytes(body)


CONTROLS = {"no-reconstruct": _no_reconstruct}


def verify(run, state: dict, result: dict) -> dict:
    reads = state["reads"]
    wrong = sum(r[3] == 1 for r in reads)
    unanswered = sum(r[3] == 2 for r in reads)
    if run.control:
        # the control's answers in the program's place, for a sample of
        # the window's reads drawn from the seed, through the same
        # comparison of digests
        rng = np.random.default_rng([run.seed, 7])
        take = rng.choice(len(reads), size=min(
            len(reads), run.traffic.get("control_sample", 64)),
            replace=False)
        wrong = 0
        for j in take:
            i = reads[int(j)][2]
            _, index = state["needles"][i]
            want = digest(payload(run.seed, index, run.bench.needle_bytes))
            wrong += digest(CONTROLS[run.control](run, state, i)) != want
    return {"reads_wrong_bytes": {"value": int(wrong), "limit": 0},
            "reads_unanswered": {"value": int(unanswered), "limit": 0}}
