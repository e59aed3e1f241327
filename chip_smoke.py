#!/usr/bin/env python3
"""chip_smoke.py — the served erasure-coding path on the attached TPU.

One process holds the chip and runs an in-process cluster (master + one
volume server with ``-ec.encoder jax``; real HTTP and gRPC ports on
loopback). Data made from ``--seed`` goes through the upload path into
sealed 1 GB volumes (RS(10,4), 1 GB / 1 MB block geometry), and every
step after that goes through the shell and the HTTP port:

  ec.encode -> EC reads -> shards lost -> degraded reads -> ec.rebuild
  -> volume.scrub

with every shard file compared byte for byte against a numpy encode of
a copy of the same .dat, and every read against the bytes stored.

Output: the first line says what was found (platform, device_kind as
reported, versions, free disk, volumes chosen); then one JSON line per
phase; the LAST line is the contract line

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A failing phase prints its traceback and its name and the run stops
there with a non-zero code and no contract line. Without a TPU the
script refuses to start. ``--rehearse`` runs the same phases at a tiny
size on the CPU and can never print the contract line.

``--chips 4`` runs only the multi-chip path: the same volumes encoded
and verified through the mesh scheduler (``-ec.mesh``) on the four-chip
mesh, compared with the one-device fleet's files and the numpy
reference, with per-device placement printed.

The script starts no child that touches JAX (``make`` builds the native
library) and stops every server it starts.
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import filecmp
import json
import os
import re
import shutil
import sys
import threading
import time
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(HERE, "chip_smoke_data")
COLLECTION = "smoke"
LOST_SHARDS = (0, 3)          # two data shards: reads must reconstruct
READS_PER_VOLUME = 20
READ_STRIDE = 7               # coprime with 10: walks every shard column
GIB = 1 << 30
# One request may wait this long. The first chip runs showed why it is
# generous: the machine's disk can stall a 1 MiB append for over 30 s
# (a POST timed out at the suite helper's 30 s with the server healthy);
# a stall slows the run, only a real hang should fail it — and then the
# failing phase dumps every thread's stack.
HTTP_TIMEOUT_S = 300.0
# No phase takes a tenth of this when the system works (the slowest,
# encode, is ~20 s cold). A phase still running after it is hung: the
# watchdog names it, dumps every thread's stack and ends the process —
# inside the driver's limit, so a hang is readable instead of a kill.
PHASE_LIMIT_S = 420.0

# real size / rehearsal size
REAL = dict(volume_mb=1024, needle_bytes=1 << 20, want_volumes=2)
TINY = dict(volume_mb=12, needle_bytes=64 << 10, want_volumes=2)
# peak disk per volume: .dat + served shards (1.4x) + reference shards
# (1.4x; the reference's .dat copy is gone by then) = 3.8 GiB; with
# --chips 4 the one-device fleet's shards come on top (5.2 GiB)
DISK_PER_VOLUME = {1: 4 * GIB, 4: 6 * GIB}
DISK_SLACK = 1 * GIB


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- compile accounting -------------------------------------------------------

class CompileClock:
    """Sums JAX's own compile events: seconds in the backend compile
    (which is the cache read on a persistent-cache hit), and persistent
    cache hits / misses (a miss is counted when the entry is written)."""

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

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits, self.misses)


# -- the run ------------------------------------------------------------------

class Smoke:
    def __init__(self, args):
        self.args = args
        self.size = TINY if args.rehearse else REAL
        self.clock = None
        self.cluster = None
        self.shell = None
        self.vids = []
        self.fids = {}            # vid -> [(fid, payload index)]
        self.bases = {}           # vid -> served base name
        self.ref = {}             # vid -> numpy reference base name
        self.one = {}             # vid -> one-device fleet base (--chips 4)
        self.dat_bytes = 0
        self.cut = None
        self.cache_dir = ""
        self.total_compile_s = 0.0
        self.t_start = time.perf_counter()

    # -- phase plumbing -------------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """Run one phase; print its line; on failure print the
        traceback, name the phase and stop the whole run."""
        from seaweedfs_tpu.stats import trace
        note(f"phase {name} ...")
        trace.clear()
        info = {"phase": name}
        c0 = self.clock.snapshot() if self.clock else (0.0, 0, 0, 0)
        a0 = self._placed0 = self._placed()
        t0 = time.perf_counter()
        watchdog = threading.Timer(PHASE_LIMIT_S, self._hung, [info])
        watchdog.daemon = True
        watchdog.start()
        try:
            yield info
        except BaseException as e:
            watchdog.cancel()
            traceback.print_exc()
            # where every thread is: a hang or a stall in a server
            # thread is then in the run's output, not lost with it
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            sys.stderr.flush()
            info.update(ok=False, seconds=round(time.perf_counter() - t0, 3),
                        error=f"{type(e).__name__}: {e}"[:500])
            say(info)
            print(f"chip_smoke: FAILED in phase {name}", flush=True)
            self.teardown()
            sys.exit(1)
        watchdog.cancel()
        secs = time.perf_counter() - t0
        c1 = self.clock.snapshot() if self.clock else c0
        compile_s = c1[0] - c0[0]
        self.total_compile_s += compile_s
        a1 = self._placed()
        spans = {}
        for s in trace.spans():
            spans[s.name] = spans.get(s.name, 0) + 1
        info.update(
            ok=True, seconds=round(secs, 3),
            # compile seconds are JAX's own, summed over threads (two
            # threads compiling at once can exceed the wall seconds)
            compile_seconds=round(compile_s, 3),
            run_seconds=round(max(0.0, secs - compile_s), 3),
            compiles=c1[1] - c0[1], cache_hits=c1[2] - c0[2],
            cache_writes=c1[3] - c0[3],
            volumes=len(self.vids), cut=self.cut,
            cache_dir=self.cache_dir,
            device_bytes={k: v - a0.get(k, 0) for k, v in a1.items()
                          if v - a0.get(k, 0)},
            spans={k: v for k, v in sorted(spans.items())
                   if k.startswith(("fleet.", "store_ec.", "scrub.",
                                    "reads.", "mesh."))})
        say(info)

    def _hung(self, info: dict) -> None:
        """Watchdog: the phase outlived PHASE_LIMIT_S. Say which, show
        where every thread is, and end the process (the main thread is
        stuck, so nothing can be unwound; the data directory stays)."""
        info.update(ok=False, seconds=PHASE_LIMIT_S,
                    error=f"still running after {PHASE_LIMIT_S:.0f} s")
        say(info)
        print(f"chip_smoke: FAILED in phase {info['phase']} (hung)",
              flush=True)
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        sys.stderr.flush()
        os._exit(1)

    def _placed(self) -> dict:
        """{"<platform>:<device id>": input bytes dispatched so far} —
        read off the arrays the program handed to its jitted kernels
        (ops/rs_kernel.placed_bytes)."""
        from seaweedfs_tpu.ops import rs_kernel
        return {f"{plat}:{dev}": n
                for (plat, dev), n in sorted(rs_kernel.placed_bytes().items())}

    def device_moved(self, every_device: bool = False) -> None:
        """Assert the device(s) did work since the current phase began:
        the kernels' input arrays were placed on this platform's
        device 0 — with `every_device`, on all of them (the phase line
        prints the same per-device deltas as `device_bytes`)."""
        import jax
        check(jax.devices()[0].platform == self.platform,
              "the JAX platform changed under the run")
        a0, a1 = self._placed0, self._placed()
        moved = {k: v - a0.get(k, 0) for k, v in a1.items()}
        want = [f"{self.platform}:{d.id}" for d in jax.devices()]
        check(moved.get(want[0], 0) > 0,
              f"no kernel input was placed on {want[0]}: {moved}")
        check(all(k in want for k, v in moved.items() if v),
              f"work was placed off the {self.platform} devices: {moved}")
        if every_device:
            check(all(moved.get(k, 0) > 0 for k in want),
                  f"work was not placed on every device: {moved}")

    def http(self, url: str, data=None, method: str = "GET"):
        req = urllib.request.Request(
            url if url.startswith("http") else f"http://{url}",
            data=data, method=method)
        return urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S)

    def http_json(self, url: str) -> dict:
        with self.http(url) as r:
            return json.load(r)

    def metrics(self) -> dict:
        """Scrape the cluster's /metrics over HTTP: name{labels} -> value,
        plus name -> sum over labels."""
        with self.http(f"{self.cluster.metrics_url}/metrics") as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if not line or line[0] == "#":
                continue
            key, _, val = line.rpartition(" ")
            try:
                v = float(val)
            except ValueError:
                continue
            out[key] = v
            bare = key.split("{", 1)[0]
            if bare != key:
                out[bare] = out.get(bare, 0.0) + v
        return out

    # -- start-up -------------------------------------------------------------

    def found(self) -> None:
        """First line: what is here. Refuses to go on without a TPU
        (unless this is the rehearsal)."""
        a = self.args
        try:
            from seaweedfs_tpu.util import compile_cache
        except ImportError as e:
            print(f"chip_smoke: the program is not here ({e}); run this "
                  "script from the root of a checkout", flush=True)
            sys.exit(2)
        try:
            import jax
            import jaxlib
            devices = jax.devices()
        except Exception as e:  # noqa: BLE001 - any init failure = no chip
            print(f"chip_smoke: no chip: JAX could not start a backend: "
                  f"{type(e).__name__}: {str(e)[:300]}", flush=True)
            sys.exit(2)
        try:
            from importlib.metadata import version
            libtpu = version("libtpu")
        except Exception:  # noqa: BLE001 - absent package is an answer
            libtpu = None
        d0 = devices[0]
        self.platform = d0.platform
        self.kind = d0.device_kind
        self.count = len(devices)
        free = shutil.disk_usage(HERE).free
        want = self.size["want_volumes"]
        if a.rehearse:
            n = want
        else:
            each = DISK_PER_VOLUME[a.chips]
            n = int(max(0, min(want, (free - DISK_SLACK) // each)))
            if n < want:
                self.cut = (f"{n} volume(s) instead of {want}: "
                            f"{free / GIB:.1f} GiB free under {HERE}, "
                            f"{each / GIB:.0f} GiB needed each")
        self.n_volumes = n
        say({"phase": "found", "platform": self.platform,
             "device_kind": self.kind, "count": self.count,
             "jax": jax.__version__, "jaxlib": jaxlib.__version__,
             "libtpu": libtpu, "python": sys.version.split()[0],
             "data_dir": DATA_DIR, "disk_free_bytes": free,
             "volumes": n, "volume_mb": self.size["volume_mb"],
             "cut": self.cut, "seed": a.seed, "chips": a.chips,
             "rehearsal": bool(a.rehearse),
             "JAX_COMPILATION_CACHE_DIR":
                 os.environ.get(compile_cache.ENV_VAR)})
        if a.rehearse:
            if self.platform != "cpu":
                print("chip_smoke: --rehearse is the CPU rehearsal; JAX "
                      f"found platform {self.platform!r}", flush=True)
                sys.exit(2)
        elif self.platform != "tpu":
            print(f"chip_smoke: no chip: JAX found platform "
                  f"{self.platform!r} ({self.kind!r} x{self.count}); this "
                  "script runs on a TPU only (--rehearse is the CPU "
                  "rehearsal and proves nothing about the chip)", flush=True)
            sys.exit(2)
        if self.count != a.chips:
            print(f"chip_smoke: asked for {a.chips} chip(s), JAX found "
                  f"{self.count}", flush=True)
            sys.exit(2)
        if n < 1:
            print(f"chip_smoke: not enough disk: {self.cut}", flush=True)
            sys.exit(2)
        self.clock = CompileClock()

    def setup(self) -> None:
        with self.phase("native") as p:
            from seaweedfs_tpu.native import rs_native
            had = not rs_native.stale()
            path = rs_native.ensure_built()
            check(rs_native.available(), rs_native.load_error())
            p.update(library=os.path.relpath(path, HERE),
                     built_now=not had)

        with self.phase("cluster") as p:
            import pathlib
            from seaweedfs_tpu.shell import Shell
            from seaweedfs_tpu.stats import trace
            from tests.cluster_util import Cluster
            shutil.rmtree(DATA_DIR, ignore_errors=True)
            os.makedirs(DATA_DIR)
            trace.enable(capacity=1 << 16)
            mesh = self.args.chips > 1
            self.cluster = Cluster(
                pathlib.Path(DATA_DIR), n_volume_servers=1,
                volume_size_limit_mb=self.size["volume_mb"],
                ec_encoder="jax",
                volume_kwargs={"ec_mesh": True} if mesh else None)
            from seaweedfs_tpu.util import compile_cache
            self.cache_dir = compile_cache.configure()
            vs = self.cluster.volume_servers[0]
            check(vs.ec_encoder == "jax" and vs.scrub.backend == "jax"
                  and vs.degraded is not None
                  and vs.degraded.backend == "jax",
                  "the volume server did not take the jax backend")
            check((vs.ec_mesh_cfg is not None) == mesh,
                  "-ec.mesh is on exactly when --chips > 1")
            self.shell = Shell(self.cluster.master.url)
            # grow exactly the volumes wanted: a fresh collection
            # otherwise spreads uploads over seven
            grown = self.http_json(
                f"{self.cluster.master.url}/vol/grow?count="
                f"{self.n_volumes}&collection={COLLECTION}")
            check(grown.get("count") == self.n_volumes, f"grow: {grown}")
            self.vids = sorted(grown["volumeIds"])
            p.update(volume_server=vs.url, backend=vs.ec_encoder,
                     ec_mesh=mesh, volume_ids=self.vids)

    # -- data -----------------------------------------------------------------

    def payload(self, index: int) -> bytes:
        import numpy as np
        return np.random.default_rng([self.args.seed, index]).bytes(
            self.size["needle_bytes"])

    def load(self) -> None:
        """Fill every grown volume past the master's size limit through
        /dir/assign + POST, so the master seals it."""
        with self.phase("load") as p:
            from seaweedfs_tpu.operation.file_id import parse_fid
            limit = self.size["volume_mb"] << 20
            per_volume = -(-limit // self.size["needle_bytes"])
            counts = {v: 0 for v in self.vids}
            fids = {v: [] for v in self.vids}
            lock = threading.Lock()
            nxt = [0]
            slowest = [0.0]

            def worker():
                while True:
                    with lock:
                        if all(c >= per_volume for c in counts.values()):
                            return
                    a = self.http_json(f"{self.cluster.master.url}"
                                       f"/dir/assign?collection={COLLECTION}")
                    check("fid" in a, f"assign: {a}")
                    vid = parse_fid(a["fid"]).volume_id
                    with lock:
                        if vid not in counts:
                            # the last slots were taken while this
                            # assign was in flight and the master, all
                            # volumes sealed, grew a new one: drop it
                            check(all(c >= per_volume
                                      for c in counts.values()),
                                  f"assign left {self.vids}: {a}")
                            return
                        if counts[vid] >= per_volume:
                            full = True   # sealed at the next heartbeat
                        else:
                            full = False
                            counts[vid] += 1
                            index = nxt[0]
                            nxt[0] += 1
                    if full:
                        time.sleep(0.02)
                        continue
                    body = self.payload(index)
                    t0 = time.perf_counter()
                    with self.http(f"{a['url']}/{a['fid']}", data=body,
                                   method="POST") as r:
                        resp = json.load(r)
                    took = time.perf_counter() - t0
                    check("error" not in resp, f"upload: {resp}")
                    with lock:
                        fids[vid].append((a["fid"], index))
                        slowest[0] = max(slowest[0], took)

            with ThreadPoolExecutor(4, thread_name_prefix="load") as pool:
                for f in [pool.submit(worker) for _ in range(4)]:
                    f.result()
            self.fids = fids
            vs = self.cluster.volume_servers[0]
            sizes = {}
            for vid in self.vids:
                v = vs.store.find_volume(vid)
                self.bases[vid] = v.file_name()
                sizes[vid] = os.path.getsize(v.file_name() + ".dat")
                check(sizes[vid] >= limit,
                      f"volume {vid} holds {sizes[vid]} < {limit} bytes")
            self.dat_bytes = sum(sizes.values())
            p.update(bytes=self.dat_bytes, needles=nxt[0],
                     dat_sizes=sizes,
                     slowest_upload_seconds=round(slowest[0], 3))

    def snapshot_and_reference(self, one_device: bool = False) -> None:
        """Seal the volumes, copy each .dat aside and encode the copy
        with the plain numpy codec — the reference every later file
        comparison uses."""
        with self.phase("reference") as p:
            from seaweedfs_tpu.ec import encoder
            for vid in self.vids:
                out = self.shell.run_command(
                    f"volume.mark -volumeId={vid} -readonly")
                check("readonly" in out, f"volume.mark: {out!r}")
            ref_dir = os.path.join(DATA_DIR, "reference")
            os.makedirs(ref_dir)
            for vid in self.vids:
                name = os.path.basename(self.bases[vid])
                self.ref[vid] = os.path.join(ref_dir, name)
                shutil.copyfile(self.bases[vid] + ".dat",
                                self.ref[vid] + ".dat")
                if one_device:
                    d = os.path.join(DATA_DIR, "one_device")
                    os.makedirs(d, exist_ok=True)
                    self.one[vid] = os.path.join(d, name)
                    shutil.copyfile(self.bases[vid] + ".dat",
                                    self.one[vid] + ".dat")

            def encode_ref(vid):
                encoder.write_ec_files(self.ref[vid], backend="numpy")
                os.remove(self.ref[vid] + ".dat")

            with ThreadPoolExecutor(len(self.vids)) as pool:
                list(pool.map(encode_ref, self.vids))
            p.update(bytes=self.dat_bytes, codec="numpy")

    def compare_shards(self, against: dict, sids=None) -> int:
        """Every served shard file equals `against`'s, byte for byte."""
        from seaweedfs_tpu.ec.encoder import shard_file_name
        from seaweedfs_tpu.ec.shard_bits import TOTAL_SHARDS
        n = 0
        for vid in self.vids:
            for sid in (range(TOTAL_SHARDS) if sids is None else sids):
                got = shard_file_name(self.bases[vid], sid)
                want = shard_file_name(against[vid], sid)
                check(os.path.exists(got), f"{got} is missing")
                check(filecmp.cmp(got, want, shallow=False),
                      f"volume {vid} shard {sid}: {got} differs from {want}")
                n += os.path.getsize(got)
        return n

    # -- served phases --------------------------------------------------------

    def wait_shards(self, want: int) -> None:
        """Until the master's topology shows `want` shards of every
        volume (heartbeats are asynchronous; a partial view would be
        cached by the serving node and fail reads)."""
        topo = self.cluster.master.topo
        for vid in self.vids:
            self.cluster.wait_for(
                lambda vid=vid: sum(
                    b.count for b in topo.lookup_ec(vid).values()) == want,
                timeout=60.0, what=f"{want} shards of volume {vid} "
                                   "registered at the master")

    def encode(self, name: str = "encode") -> None:
        with self.phase(name) as p:
            m0 = self.metrics()
            vids = ",".join(str(v) for v in self.vids)
            out = self.shell.run_command(
                f"ec.encode -volumeId={vids} -encoder=jax")
            for vid in self.vids:
                check(f"volume {vid}: ec.encode done" in out,
                      f"ec.encode: {out!r}")
            m1 = self.metrics()
            self.device_moved(every_device=self.args.chips > 1)
            p["bytes"] = self.dat_bytes
            if self.args.chips > 1:
                moved = m1.get('SeaweedFS_fleet_mesh_buckets_total'
                               '{op="encode"}', 0.0) - \
                    m0.get('SeaweedFS_fleet_mesh_buckets_total'
                           '{op="encode"}', 0.0)
                check(moved > 0, "no mesh bucket was dispatched")
                p["mesh_buckets"] = moved
                self.no_fallback(m0, m1, p)
            else:
                key = "SeaweedFS_fleet_dispatched_bytes_total"
                moved = m1.get(key, 0.0) - m0.get(key, 0.0)
                grouped = len(self.vids) > 1
                # one volume takes the per-volume route, which has no
                # fleet counter; the device check above still holds
                check(moved >= self.dat_bytes or not grouped,
                      f"fleet dispatched {moved} of {self.dat_bytes} bytes")
                p.update(fleet_dispatched_bytes=moved,
                         route="generate_ec_shards_batch -> ec/fleet"
                         if grouped else "generate_ec_shards")
            self.wait_shards(14)
            p["compared_bytes"] = self.compare_shards(self.ref)
            if self.one:
                p["compared_bytes_one_device"] = \
                    self.compare_shards(self.one)

    def no_fallback(self, m0: dict, m1: dict, p: dict) -> None:
        key = "SeaweedFS_fleet_mesh_fallbacks_total"
        fell = m1.get(key, 0.0) - m0.get(key, 0.0)
        p["mesh_fallbacks"] = fell
        check(fell == 0, f"the mesh path fell back {fell} time(s): " + str(
            {k: v for k, v in m1.items() if k.startswith(key + "{")}))

    def picks(self):
        """(fid, payload index) of the needles read back: a stride that
        walks every shard column of the first rows of each volume."""
        out = []
        for vid in self.vids:
            out += self.fids[vid][::READ_STRIDE][:READS_PER_VOLUME]
        return out

    def read_back(self, name: str, degraded: bool) -> None:
        with self.phase(name) as p:
            m0 = self.metrics()
            picks = self.picks()

            def get(item):
                fid, index = item
                lk = self.http_json(f"{self.cluster.master.url}"
                                    f"/dir/lookup?volumeId={fid}")
                check(lk.get("locations"), f"lookup {fid}: {lk}")
                with self.http(f"{lk['locations'][0]['url']}/{fid}") as r:
                    body = r.read()
                check(body == self.payload(index),
                      f"GET {fid}: {len(body)} bytes differ from what "
                      "was stored")
                return len(body)

            with ThreadPoolExecutor(4, thread_name_prefix="get") as pool:
                n = sum(pool.map(get, picks))
            m1 = self.metrics()
            p.update(bytes=n, reads=len(picks))
            if degraded:
                self.device_moved()
                vs = self.cluster.volume_servers[0]
                key = "SeaweedFS_reads_degraded_total"
                moved = m1.get(key, 0.0) - m0.get(key, 0.0)
                check(moved > 0 and vs.degraded.dispatches > 0,
                      "no read went through the degraded decode fleet")
                p.update(
                    degraded_intervals=moved,
                    decode_dispatches=vs.degraded.dispatches,
                    decoded_bytes=m1.get(
                        "SeaweedFS_reads_decoded_bytes_total", 0.0) -
                    m0.get("SeaweedFS_reads_decoded_bytes_total", 0.0))

    def degrade(self) -> None:
        """Lose LOST_SHARDS of every volume the way an operator's
        tooling drops shards: unmount + delete through the shell's own
        volume-server client."""
        with self.phase("degrade") as p:
            from seaweedfs_tpu.ec.encoder import shard_file_name
            from seaweedfs_tpu.pb import volume_server_pb2 as pb
            url = self.cluster.volume_servers[0].url
            stub = self.shell.env.volume_server(url)
            for vid in self.vids:
                stub.VolumeEcShardsUnmount(pb.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=list(LOST_SHARDS)))
                stub.VolumeEcShardsDelete(pb.VolumeEcShardsDeleteRequest(
                    volume_id=vid, collection=COLLECTION,
                    shard_ids=list(LOST_SHARDS)))
                for sid in LOST_SHARDS:
                    check(not os.path.exists(
                        shard_file_name(self.bases[vid], sid)),
                        f"volume {vid} shard {sid} is still on disk")
            self.wait_shards(14 - len(LOST_SHARDS))
            p.update(lost_shards=list(LOST_SHARDS))

    def rebuild(self) -> None:
        with self.phase("rebuild") as p:
            out = self.shell.run_command("ec.rebuild -encoder=jax")
            for vid in self.vids:
                check(f"volume {vid}: rebuilt shards "
                      f"{list(LOST_SHARDS)}" in out, f"ec.rebuild: {out!r}")
            self.device_moved()
            self.wait_shards(14)
            p["bytes"] = self.compare_shards(self.ref, sids=LOST_SHARDS)
            p["compared_bytes"] = self.compare_shards(self.ref)

    def scrub(self, name: str = "scrub") -> None:
        with self.phase(name) as p:
            m0 = self.metrics()
            # returns when the pass has ended; a pass that failed fails
            # the command (the server's log says why)
            out = self.shell.run_command("volume.scrub -full -wait")
            check("scrub started" in out, f"volume.scrub: {out!r}")
            m = re.search(
                r": (\w+) passes:(\d+) scanned:(\d+)B needles:(\d+) "
                r"stripes:(\d+) found:(\d+) repaired:(\d+) "
                r"unrecoverable:(\d+)", out)
            check(m is not None and m.group(1) == "idle"
                  and int(m.group(2)) == 1, f"volume.scrub -wait: {out!r}")
            for vid in self.vids:
                check(f": volume {vid}: clean" in out,
                      f"volume {vid} is not clean: {out!r}")
            m1 = self.metrics()
            _, _, scanned, needles, stripes, found, repaired, lost = \
                (int(x) if x.isdigit() else x for x in m.groups())
            p.update(bytes=scanned, needles=needles, stripes=stripes,
                     found=found, repaired=repaired, unrecoverable=lost)
            check(found == 0 and repaired == 0 and lost == 0,
                  f"scrub found damage: {out}")
            check(stripes > 0 and needles > 0,
                  f"scrub verified nothing: {out}")
            self.device_moved(every_device=self.args.chips > 1)
            if self.args.chips > 1:
                key = 'SeaweedFS_fleet_mesh_buckets_total{op="verify"}'
                moved = m1.get(key, 0.0) - m0.get(key, 0.0)
                check(moved > 0, "no mesh verify bucket was dispatched")
                p["mesh_buckets"] = moved
                self.no_fallback(m0, m1, p)

    def one_device_fleet(self) -> None:
        """--chips 4: what the mesh is compared with — the same .dat
        copies through the one-device fleet scheduler."""
        with self.phase("one_device_fleet") as p:
            import jax
            from seaweedfs_tpu.ec import fleet
            fleet.fleet_write_ec_files(
                [self.one[v] for v in self.vids], backend="jax",
                device=jax.devices()[0])
            self.device_moved()
            for vid in self.vids:
                os.remove(self.one[vid] + ".dat")
            p["bytes"] = self.dat_bytes

    # -- tear-down ------------------------------------------------------------

    def teardown(self) -> None:
        if self.cluster is not None:
            try:
                self.cluster.stop()
            except Exception:  # noqa: BLE001 - report, keep cleaning
                traceback.print_exc()
            self.cluster = None
        shutil.rmtree(DATA_DIR, ignore_errors=True)

    def run(self) -> int:
        a = self.args
        self.found()
        self.setup()
        self.load()
        if a.chips > 1:
            self.snapshot_and_reference(one_device=True)
            self.one_device_fleet()
            self.encode("mesh_encode")
            self.scrub("mesh_verify")
        else:
            self.snapshot_and_reference()
            self.encode()
            self.read_back("ec_read", degraded=False)
            self.degrade()
            self.read_back("degraded_read", degraded=True)
            self.rebuild()
            self.scrub()
        with self.phase("stop") as p:
            self.teardown()
            check(not os.path.exists(DATA_DIR),
                  f"{DATA_DIR} was not removed")
            p.update(total_seconds=round(
                time.perf_counter() - self.t_start, 3),
                total_compile_seconds=round(self.total_compile_s, 3))
        if a.rehearse:
            say({"ok": True, "rehearsal": True,
                 "note": "CPU rehearsal at a tiny size: proves the "
                         "control flow, says nothing about the chip"})
        else:
            say({"ok": True, "device": {"platform": self.platform,
                                        "kind": self.kind,
                                        "count": self.count}})
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Drive the served EC path once on the attached TPU.")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the uploaded data")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path and what it is "
                         "compared with, on a four-chip host")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: the same phases at a tiny size; "
                         "never prints the contract line")
    args = ap.parse_args(argv)
    if args.rehearse:
        # the one switch that holds the run to the CPU — set before jax
        # is imported, with as many virtual devices as chips asked for
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.chips}").strip()
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return Smoke(args).run()


if __name__ == "__main__":
    sys.exit(main())
