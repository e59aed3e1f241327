#!/usr/bin/env python3
"""The client side of ``http_reads``: closed-loop GETs from a process of
its own, which never imports jax — so the readers do not share the
server's interpreter lock and cannot touch the chip.

Protocol, on standard input and output: the parent writes one JSON line
(seed, needle size, clients, the server's address and the needles as
``[fid, payload index]``); this process remakes every needle's payload
from ``(seed, index)``, keeps its digest, opens one keep-alive
connection a client and answers ``ready``. On ``go <seconds>`` every
client draws needles (seeded, uniform), GETs them one after another and
checks each body against the digest, until the seconds are over; the
request in flight is finished. Then one JSON line goes back: for every
GET its start and end on the monotonic clock (which parent and child
share on Linux), the needle and 0 right / 1 wrong bytes / 2 no answer;
and the CPU seconds this process spent in the window.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import sys
import threading
import time

import numpy as np

TIMEOUT_S = 300.0


def digest(body: bytes) -> bytes:
    return hashlib.blake2b(body, digest_size=16).digest()


def main() -> int:
    job = json.loads(sys.stdin.readline())
    seed, nbytes = job["seed"], job["needle_bytes"]
    needles = job["needles"]
    want = [digest(np.random.default_rng([seed, index]).bytes(nbytes))
            for _, index in needles]
    conns = [http.client.HTTPConnection(job["host"], job["port"],
                                        timeout=TIMEOUT_S)
             for _ in range(job["clients"])]
    for c in conns:
        c.connect()
    print("ready", flush=True)
    line = sys.stdin.readline().split()
    if not line or line[0] != "go":
        return 1
    seconds = float(line[1])
    t_end = time.perf_counter() + seconds
    cpu0 = time.process_time()
    out = [[] for _ in conns]

    def client(k: int) -> None:
        rng = np.random.default_rng([seed, 1000003, k])
        conn = conns[k]
        while time.perf_counter() < t_end:
            i = int(rng.integers(len(needles)))
            t0 = time.perf_counter()
            try:
                conn.request("GET", "/" + needles[i][0])
                resp = conn.getresponse()
                body = resp.read()
                t1 = time.perf_counter()
                if resp.status != 200:
                    code = 2
                else:
                    code = 0 if digest(body) == want[i] else 1
            except (OSError, http.client.HTTPException):
                t1 = time.perf_counter()
                code = 2
                conn.close()
                conn = http.client.HTTPConnection(
                    job["host"], job["port"], timeout=TIMEOUT_S)
            out[k].append((t0, t1, i, code))
        conn.close()

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(len(conns))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    print(json.dumps({"reads": [r for rows in out for r in rows],
                      "cpu_s": time.process_time() - cpu0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
