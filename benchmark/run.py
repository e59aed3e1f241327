#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip and runs the system under test: an in-process
cluster (master + one volume server, ``ec_encoder="jax"``, real HTTP and
gRPC ports on loopback). The run makes its data from ``--seed``, loads
it through the upload path, warms the cell's kernel shapes, measures
for ``--seconds`` and then decides ``correct`` against the plain
reference in ``benchmark/reference.py``.

Everything that belongs to one cell is data, found by the names in
``BENCHMARK.json``: the configuration (``configs/<config>.json``), the
traffic mix (``traffic/<traffic>.json``, which names its generator in
``drivers/``) and each per-layer metric (``layer_metrics/<metric>.json``,
which names its reader in ``readers/``).

The LAST line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``compared``: each number that decided
``correct`` beside its limit. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Without a TPU (or with fewer chips than the cell asks for, or a
``device_kind`` that ``peaks.json`` does not know) the run exits
non-zero and prints no result. ``--rehearse`` runs the same code at a
tiny size on the CPU; it proves the control flow and can never print
the result line.
"""

from __future__ import annotations

import argparse
import faulthandler
import importlib
import json
import os
import shutil
import sys
import threading
import time
import traceback

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_DIR = os.path.join(ROOT, ".bench_data")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# A run has 360 s; one still going after this is hung. Say where every
# thread is and end the process, so that a hang is readable.
RUN_LIMIT_S = 345.0


def note(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def say(obj: dict) -> None:
    """An earlier line of standard output (the result is the last)."""
    print(json.dumps(obj), flush=True)


def refuse(msg: str, code: int = 2):
    print(f"benchmark: {msg}", flush=True)
    sys.exit(code)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    refuse(f"no {what} named {name!r} in BENCHMARK.json "
           f"(there: {[it['name'] for it in items]})")


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


class Tracer:
    """The profiler around a part of the window, from the process that
    holds the chip. ``start`` notes a host-clock instant beside an
    annotation in the trace, which puts host spans on the trace's clock."""

    def __init__(self, on: bool, log_dir: str):
        self.on = on
        self.log_dir = log_dir
        self.t0 = self.t1 = None           # host clock
        self.sync_host = None
        self._lock = threading.Lock()

    def start(self) -> None:
        if not self.on or self.t0 is not None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # server threads are Python: too much
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.sync_host = time.perf_counter()
        with jax.profiler.TraceAnnotation("benchmark.sync"):
            pass
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        with self._lock:
            if not self.on or self.t0 is None or self.t1 is not None:
                return
            self.t1 = time.perf_counter()
        import jax
        jax.profiler.stop_trace()

    def stop_after(self, seconds: float) -> None:
        if self.on:
            t = threading.Timer(seconds, self.stop)
            t.daemon = True
            t.start()

    def reduced(self, host_spans) -> dict | None:
        """The traced part reduced (see trace_reduce), or None when
        nothing was traced or the trace holds no device plane."""
        if self.t0 is None:
            return None
        self.stop()
        from benchmark import trace_reduce
        loaded = trace_reduce.load(trace_reduce.newest_xplane(self.log_dir))
        for plane, line, n in loaded["summary"]:
            note(f"trace: plane {plane!r} line {line!r}: {n} events")
        if not loaded["devices"] or loaded["sync_s"] is None:
            note("trace: no device plane or no sync annotation")
            return None
        shift = loaded["sync_s"] - self.sync_host     # host clock -> trace
        spans = [(n, t + shift, d) for n, t, d in host_spans]
        return trace_reduce.reduce(loaded["devices"], self.t0 + shift,
                                   self.t1 + shift, spans)


def device_or_exit(chips: int, rehearse: bool) -> dict:
    try:
        import seaweedfs_tpu  # noqa: F401 - the program has to be here
        from tests import cluster_util  # noqa: F401
    except ImportError as e:
        refuse(f"the program is not here ({e}); run from the root of a "
               "checkout")
    try:
        import jax
        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 - any init failure = no chip
        refuse("no chip: JAX could not start a backend: "
               f"{type(e).__name__}: {str(e)[:300]}")
    d0 = devices[0]
    found = {"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devices)}
    if rehearse:
        if d0.platform != "cpu":
            refuse(f"--rehearse is the CPU rehearsal; JAX found {found}")
        return found
    if d0.platform != "tpu":
        refuse(f"no chip: JAX found {found}; this benchmark runs on a TPU "
               "only (--rehearse is the CPU rehearsal and proves nothing "
               "about the chip)")
    if len(devices) != chips:
        refuse(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    from benchmark import work
    try:
        work.peaks(d0.device_kind)
    except work.UnknownDeviceKind as e:
        refuse(str(e))
    return found


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def warm_up(warm: dict) -> int:
    """Every shape the program's dispatch can meet for the cell's
    matrices, as the traffic mix's file lists them: the matrices'
    shapes and the lane widths (powers of two; today `_submit_slabs`
    pads to 2^16 .. 2^22). Through the program's own entry, so that the
    same programs are in the cache as the window uses. A shape that
    the list misses compiles inside the window, and that run gives no
    result (see `measure`)."""
    import numpy as np
    from seaweedfs_tpu.ops import rs_kernel
    from benchmark import reference
    lo, hi = warm["lane_widths_log2"]
    n = 0
    for m in warm["matrices"]:
        # the compiled program depends on the matrix's shape alone
        matrix = reference.parity_matrix()[:m["out_rows"], :m["in_rows"]]
        for shift in range(lo, hi + 1):
            zeros = np.zeros((m["in_rows"], 1 << shift), dtype=np.uint8)
            rs_kernel.apply_matrix_async(matrix, zeros).result()
            n += 1
    return n


class Run:
    """What a driver gets: the cell's data, the cluster, the clocks."""

    def __init__(self, args, cell, config, traffic, device, bench, tracer,
                 clock):
        self.args = args
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.device = device
        self.bench = bench
        self.tracer = tracer
        self.clock = clock
        self.seed = args.seed
        self.seconds = args.seconds
        self.rehearse = args.rehearse
        self.control = args.control
        self.data_dir = DATA_DIR
        self.children = []                 # processes a driver started
        self.note = note
        self.say = say


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; never prints the "
                         "result line")
    ap.add_argument("--control", default="",
                    help="put the named control of the cell's driver in "
                         "the program's place before the comparison: the "
                         "run then has to come out as not correct")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        # the program's compile cache goes where the environment says,
        # else to one fixed directory inside the checkout
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    # this file's directory off the path (its tests/ would shadow the
    # program's), the checkout's root on it
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or os.getcwd()) != HERE]
    sys.path.insert(0, ROOT)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = find(manifest["workloads"], args.workload, "workload")
    cfg_entry = find(manifest["configs"], cell["config"], "config")
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")

    device = device_or_exit(cell["chips"], args.rehearse)
    watchdog = threading.Timer(RUN_LIMIT_S, hung)
    watchdog.daemon = True
    watchdog.start()

    from benchmark import cluster as cl
    from benchmark import work
    size = dict(config)
    if args.rehearse:
        size.update(config["rehearsal"])
    cluster_args = dict(config["cluster"],
                        volume_size_limit_mb=size["volume_size_limit_mb"])
    # a needle size that a source gives stands at the file's top level,
    # one that was assumed under "assumed"
    needle_bytes = size.get("needle_bytes") or config["assumed"]["needle_bytes"]
    bench = cl.Bench(DATA_DIR, cluster_args, args.seed, needle_bytes)
    tracer = Tracer(bool(args.trace), os.path.join(DATA_DIR, "trace"))
    clock = cl.CompileClock()
    run = Run(args, cell, config, traffic, device, bench, tracer, clock)
    say({"found": device, "cell": cell["name"], "seed": args.seed,
         "seconds": args.seconds, "trace": args.trace,
         "rehearsal": args.rehearse, "control": args.control,
         "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR")})
    line = None
    try:
        line = measure(run, driver, manifest, size, work)
    except Exception as e:  # noqa: BLE001 - the boundary: say it, no result
        traceback.print_exc()
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        print(f"benchmark: FAILED, no result: {type(e).__name__}: "
              f"{str(e)[:500]}", flush=True)
    finally:
        for child in run.children:
            if child.poll() is None:
                child.kill()
            child.wait()
        try:
            bench.stop()
        except Exception:  # noqa: BLE001 - report, keep cleaning
            traceback.print_exc()
        shutil.rmtree(DATA_DIR, ignore_errors=True)
        watchdog.cancel()
    if line is None:
        return 1
    for name, c in line["compared"].items():
        note(f"compared {name}: {c['value']} (limit {c['limit']})")
    if args.rehearse:
        say({"rehearsal": True, "would_be": line,
             "note": "CPU rehearsal at a tiny size: proves the control "
                     "flow, says nothing about the chip"})
    else:
        say(line)
    return 0


def hung() -> None:
    print(f"benchmark: FAILED, no result: still running after "
          f"{RUN_LIMIT_S:.0f} s", flush=True)
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    sys.stderr.flush()
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    os._exit(3)


def measure(run: Run, driver, manifest: dict, size: dict, work) -> dict:
    """Set-up, window, comparison; returns the result line."""
    from seaweedfs_tpu.stats import trace as spans
    args, bench, cell = run.args, run.bench, run.cell["name"]
    t = time.perf_counter

    # -- set-up ---------------------------------------------------------------
    t0 = t()
    bench.start(size["volumes"])
    t_started = t()
    loaded = bench.load(size["volume_size_limit_mb"] << 20,
                        run.config["assumed"]["loader_threads"])
    t_loaded = t()
    bench.seal()
    state = driver.prepare(run)
    t_prepared = t()
    warmed = warm_up(run.traffic["warm_up"])
    if args.trace:
        spans.enable(capacity=1 << 17)
    spans.clear()
    setup = dict(run.clock.snapshot(), cluster_s=t_started - t0,
                 load_s=t_loaded - t_started, prepare_s=t_prepared - t_loaded,
                 warm_up_s=t() - t_prepared, warmed_shapes=warmed, **loaded)
    say({"setup": setup})

    # -- the window -----------------------------------------------------------
    placed0 = bench.placed_bytes()
    c0 = run.clock.snapshot()
    m0 = bench.metrics()
    t_window = t()
    setup_s = t_window - T_PROCESS
    result = driver.window(run, state)
    t_closed = t()
    run.tracer.stop()
    m1 = bench.metrics()
    c1 = run.clock.snapshot()
    placed = bench.placed_bytes() - placed0
    peak = memory_peak_bytes()
    host_spans = [(s.name, s.t0, s.dur) for s in spans.spans()]
    compiles = c1["compiles"] - c0["compiles"]
    say({"window": {"seconds": t_closed - t_window,
                    "compiles_in_window": compiles,
                    "compile_seconds_in_window":
                        c1["seconds"] - c0["seconds"],
                    "device_bytes_placed": placed, **result.get("notes", {})}})
    if compiles:
        raise RuntimeError(
            f"{compiles} program(s) compiled inside the window: the "
            "traffic mix's warm_up list misses a shape the program now "
            "dispatches, so the window timed compilation")
    if placed <= 0:
        raise RuntimeError("no kernel input was placed on the device in "
                           "the window: the device path was not driven")

    # -- after the window: the program goes, the reference comes --------------
    reduced = run.tracer.reduced(host_spans) if args.trace else None
    bench.stop_cluster()
    compared = driver.verify(run, state, result)
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    metrics = {}
    if args.trace:
        ctx = {"metrics0": m0, "metrics1": m1, "spans": host_spans,
               "trace": reduced, "work": result.get("work", {}),
               "driver": dict(result.get("notes", {}),
                              **result["end_to_end"]),
               "peaks": None if run.rehearse else
               work.peaks(run.device["kind"])}
        for m in manifest["per_layer"]:
            if not applies(m, cell):
                continue
            spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            value = reader.read(ctx, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        for m in manifest["end_to_end"]:
            if applies(m, cell):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    device = dict(run.device, memory_peak_bytes=peak)
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    if run.control:
        line["control"] = run.control
    line["compared"] = compared
    for name, m in metrics.items():
        if m["unit"] == "%" and m["value"] > 100.0:
            raise RuntimeError(f"{name} reads over 100%: the work is "
                               f"counted too high or time is left out: {m}")
    return line


if __name__ == "__main__":
    sys.exit(main())
