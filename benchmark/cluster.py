"""Bring-up of the system under test: the pieces ``chip_smoke.py`` proved
on the chip (PR 22), copied so that the benchmark does not change when
the smoke does.

One process holds the chip and runs the in-process cluster: a master
and one volume server with ``ec_encoder="jax"``, real HTTP and gRPC
ports on loopback. The cluster itself is the program's own bring-up
helper, ``tests/cluster_util.Cluster`` — imported, not copied (see
PERF.md for the two couplings that leaves).
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from http.client import HTTPConnection, RemoteDisconnected
from typing import Dict, List, Tuple

import numpy as np

COLLECTION = "bench"
# One request may wait this long: with the server healthy a POST once
# took 63 s on the chip machine, which a 30 s limit turned into a failed
# run (PERF.md, PR 22; the cause is at KeepAlive, below).
HTTP_TIMEOUT_S = 300.0


class BenchFailure(Exception):
    """The system did not do what the run needs; no result is printed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


class CompileClock:
    """Sums JAX's own compile events: seconds in the backend compile
    (which is the cache read on a persistent-cache hit) and persistent
    cache hits / misses."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_writes": self.misses}


def payload(seed: int, index: int, nbytes: int) -> bytes:
    """The bytes of needle ``index``: made from the seed, so that any
    process can remake them."""
    return np.random.default_rng([seed, index]).bytes(nbytes)


def http(url: str, data=None, method: str = "GET"):
    req = urllib.request.Request(
        url if url.startswith("http") else f"http://{url}",
        data=data, method=method)
    return urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S)


def http_json(url: str) -> dict:
    with http(url) as r:
        return json.load(r)


class KeepAlive:
    """One thread's connections, one a server, kept open from request to
    request. The loader makes thousands of requests; a new connection
    for each now and then drew a local port that the kernel still held
    in TIME_WAIT, and that connect then sat out the SYN retries:
    63.0 s, in about one run in four (PERF.md, PR 24)."""

    def __init__(self):
        self.conns: Dict[str, HTTPConnection] = {}

    def request_json(self, method: str, url: str, body=None) -> dict:
        address, _, path = url.removeprefix("http://").partition("/")
        for fresh in (False, True):
            conn = self.conns.get(address)
            if conn is None or fresh:
                host, _, port = address.partition(":")
                conn = self.conns[address] = HTTPConnection(
                    host, int(port), timeout=HTTP_TIMEOUT_S)
            try:
                conn.request(method, "/" + path, body=body)
                return json.load(conn.getresponse())
            except (RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                # the server closed an idle connection: once more, on a
                # new one
                conn.close()
                if fresh:
                    raise

    def close(self) -> None:
        for conn in self.conns.values():
            conn.close()


class Bench:
    """The cluster, its volumes and what was stored in them."""

    def __init__(self, data_dir: str, cluster_args: dict, seed: int,
                 needle_bytes: int):
        self.data_dir = data_dir
        self.cluster_args = cluster_args
        self.seed = seed
        self.needle_bytes = needle_bytes
        self.cluster = None
        self.shell = None
        self.vids: List[int] = []
        self.fids: Dict[int, List[Tuple[str, int]]] = {}   # vid -> [(fid, index)]
        self.bases: Dict[int, str] = {}                    # vid -> file base
        self.dat_sizes: Dict[int, int] = {}

    # -- start and stop -------------------------------------------------------

    def start(self, n_volumes: int) -> None:
        from seaweedfs_tpu.native import rs_native
        from seaweedfs_tpu.shell import Shell
        from seaweedfs_tpu.util import compile_cache
        from tests.cluster_util import Cluster
        rs_native.ensure_built()
        check(rs_native.available(), str(rs_native.load_error()))
        shutil.rmtree(self.data_dir, ignore_errors=True)
        os.makedirs(self.data_dir)
        self.cluster = Cluster(pathlib.Path(self.data_dir),
                               **self.cluster_args)
        self.cache_dir = compile_cache.configure()
        vs = self.volume_server
        check(vs.ec_encoder == "jax" and vs.degraded is not None
              and vs.degraded.backend == "jax",
              "the volume server did not take the jax backend")
        self.shell = Shell(self.cluster.master.url)
        # grow exactly the volumes wanted: a fresh collection otherwise
        # spreads uploads over seven
        grown = http_json(f"{self.cluster.master.url}/vol/grow?count="
                          f"{n_volumes}&collection={COLLECTION}")
        check(grown.get("count") == n_volumes, f"grow: {grown}")
        self.vids = sorted(grown["volumeIds"])

    @property
    def volume_server(self):
        return self.cluster.volume_servers[0]

    def stop_cluster(self) -> None:
        """Stop the servers; the files stay for the comparison."""
        if self.cluster is not None:
            try:
                self.cluster.stop()
            finally:
                self.cluster = None

    def stop(self) -> None:
        try:
            self.stop_cluster()
        finally:
            shutil.rmtree(self.data_dir, ignore_errors=True)

    # -- data -----------------------------------------------------------------

    def load(self, volume_bytes: int, threads: int) -> dict:
        """Fill every grown volume past the master's size limit through
        /dir/assign + POST, so that the master seals it."""
        from seaweedfs_tpu.operation.file_id import parse_fid
        per_volume = -(-volume_bytes // self.needle_bytes)
        counts = {v: 0 for v in self.vids}
        fids: Dict[int, list] = {v: [] for v in self.vids}
        lock = threading.Lock()
        nxt = [0]
        slowest = [0.0]
        master = self.cluster.master.url

        def worker():
            web = KeepAlive()
            try:
                work(web)
            finally:
                web.close()

        def work(web):
            while True:
                with lock:
                    if all(c >= per_volume for c in counts.values()):
                        return
                a = web.request_json(
                    "GET", f"{master}/dir/assign?collection={COLLECTION}")
                check("fid" in a, f"assign: {a}")
                vid = parse_fid(a["fid"]).volume_id
                with lock:
                    if vid not in counts:
                        # the last slots went while this assign was in
                        # flight and the master, all volumes sealed,
                        # grew a new one: drop it
                        check(all(c >= per_volume for c in counts.values()),
                              f"assign left {self.vids}: {a}")
                        return
                    full = counts[vid] >= per_volume
                    if not full:
                        counts[vid] += 1
                        index = nxt[0]
                        nxt[0] += 1
                if full:                      # sealed at the next heartbeat
                    time.sleep(0.02)
                    continue
                body = payload(self.seed, index, self.needle_bytes)
                t0 = time.perf_counter()
                resp = web.request_json("POST", f"{a['url']}/{a['fid']}",
                                        body)
                took = time.perf_counter() - t0
                check("error" not in resp, f"upload: {resp}")
                with lock:
                    fids[vid].append((a["fid"], index))
                    slowest[0] = max(slowest[0], took)

        with ThreadPoolExecutor(threads, thread_name_prefix="load") as pool:
            for f in [pool.submit(worker) for _ in range(threads)]:
                f.result()
        self.fids = fids
        for vid in self.vids:
            v = self.volume_server.store.find_volume(vid)
            self.bases[vid] = v.file_name()
            self.dat_sizes[vid] = os.path.getsize(v.file_name() + ".dat")
            check(self.dat_sizes[vid] >= volume_bytes,
                  f"volume {vid} holds {self.dat_sizes[vid]} < "
                  f"{volume_bytes} bytes")
        return {"needles": nxt[0], "dat_bytes": sum(self.dat_sizes.values()),
                "slowest_upload_s": slowest[0]}

    def seal(self) -> None:
        for vid in self.vids:
            out = self.shell.run_command(
                f"volume.mark -volumeId={vid} -readonly")
            check("readonly" in out, f"volume.mark: {out!r}")

    def keep_dat(self, vid: int) -> str:
        """A second name for a sealed volume's ``.dat``, which survives
        ``ec.encode`` deleting the volume: the reference's input. A hard
        link where the file system has them, else a copy."""
        keep = os.path.join(self.data_dir, "kept")
        os.makedirs(keep, exist_ok=True)
        dst = os.path.join(keep, f"{vid}.dat")
        src = self.bases[vid] + ".dat"
        try:
            os.link(src, dst)
        except OSError:
            shutil.copyfile(src, dst)
        return dst

    def shard_paths(self, vid: int) -> List[str]:
        return [f"{self.bases[vid]}.ec{sid:02d}" for sid in range(14)]

    # -- the master's view ----------------------------------------------------

    def wait_shards(self, vids, want: int, timeout: float = 60.0) -> None:
        """Until the master's topology shows ``want`` shards of every
        volume (heartbeats are asynchronous; a partial view would be
        cached by the serving node and fail reads)."""
        topo = self.cluster.master.topo
        for vid in vids:
            self.cluster.wait_for(
                lambda vid=vid: sum(
                    b.count for b in topo.lookup_ec(vid).values()) == want,
                timeout=timeout,
                what=f"{want} shards of volume {vid} at the master")

    def degrade(self, lost_shards) -> None:
        """Lose shards the way an operator's tooling drops them: unmount
        and delete through the volume server's gRPC."""
        from seaweedfs_tpu.pb import volume_server_pb2 as pb
        stub = self.shell.env.volume_server(self.volume_server.url)
        lost = list(lost_shards)
        for vid in self.vids:
            stub.VolumeEcShardsUnmount(pb.VolumeEcShardsUnmountRequest(
                volume_id=vid, shard_ids=lost))
            stub.VolumeEcShardsDelete(pb.VolumeEcShardsDeleteRequest(
                volume_id=vid, collection=COLLECTION, shard_ids=lost))
            for sid in lost:
                check(not os.path.exists(self.shard_paths(vid)[sid]),
                      f"volume {vid} shard {sid} is still on disk")
        self.wait_shards(self.vids, 14 - len(lost))

    # -- counters -------------------------------------------------------------

    def metrics(self) -> Dict[str, float]:
        """Scrape the cluster's /metrics: ``name{labels}`` -> value."""
        with http(f"{self.cluster.metrics_url}/metrics") as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if not line or line[0] == "#":
                continue
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                continue
        return out

    def placed_bytes(self) -> int:
        """Bytes the program handed to its jitted kernels so far."""
        from seaweedfs_tpu.ops import rs_kernel
        return sum(rs_kernel.placed_bytes().values())
