"""From a profiler trace to device busy time, device-op time and idle gaps.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
nothing else; the arithmetic below works on plain ``(name, start,
seconds)`` tuples, so it is tested on hand-made lists without a trace.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip
named ``/device:TPU:<n>``; on it the line ``XLA Ops`` has one event per
operation the chip ran (fusions, copies, custom calls), ``XLA Modules``
one per program, ``Steps`` one per step. Busy time is the union of the
``XLA Ops`` intervals; the device time of the compute operations is
their sum. Host threads are lines of the plane ``/host:CPU``; the
harness writes a ``benchmark.sync`` annotation there at a host-clock
instant it notes, which puts the program's own spans on the trace's
clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start s, duration s

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SYNC_NAME = "benchmark.sync"


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, device_prefix: str = DEVICE_PLANE_PREFIX) -> dict:
    """``{"devices": {plane: [Event]}, "sync_s": start of the sync
    annotation on the trace's clock or None, "summary": [...]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    sync_s: Optional[float] = None
    summary = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            summary.append((plane.name, line.name, len(events)))
            if plane.name.startswith(device_prefix) and \
                    line.name == OPS_LINE:
                devices.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in events)
            elif sync_s is None and plane.name.startswith("/host:"):
                for e in events:
                    if e.name == SYNC_NAME:
                        sync_s = e.start_ns / 1e9
                        break
    return {"devices": devices, "sync_s": sync_s, "summary": summary}


def busy_union(events: Iterable[Event]) -> Tuple[float, List[Tuple[float, float]]]:
    """Seconds covered by at least one event, and the merged intervals."""
    ivals = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[Tuple[float, float]] = []
    for a, b in ivals:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return sum(b - a for a, b in merged), merged


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


_HLO = re.compile(r"^(%\S+) = (\w+\[[^\]]*\])\S* ([\w\-]+)\(")


def short_name(name: str) -> str:
    """An event of ``XLA Ops`` is named by its whole HLO instruction;
    keep the result's name, the operation and the result's shape."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name[:96]


def op_totals(events: Iterable[Event]) -> List[Tuple[str, float]]:
    """Device seconds by operation, largest first."""
    total: Dict[str, float] = {}
    for name, _, d in events:
        name = short_name(name)
        total[name] = total.get(name, 0.0) + d
    return sorted(total.items(), key=lambda kv: -kv[1])


def idle_gaps(merged: Sequence[Tuple[float, float]], t0: float,
              t1: float) -> List[Tuple[float, float]]:
    """The intervals of [t0, t1] in which no operation ran."""
    gaps = []
    cur = t0
    for a, b in merged:
        if a > cur:
            gaps.append((cur, min(a, t1)))
        cur = max(cur, b)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    return [(a, b) for a, b in gaps if b > a]


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Seconds that two sorted lists of disjoint intervals share."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Event],
               none: str = "no span open") -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: for each span name, the
    seconds of the gaps during which at least one span of that name was
    open (spans nest and run on many threads, so the names overlap and
    do not add up to the idle time), and the idle seconds with no span
    open at all. Largest first."""
    by_name: Dict[str, List[Event]] = {}
    for ev in host_spans:
        by_name.setdefault(ev[0], []).append(ev)
    gaps = sorted(gaps)
    out = {name: overlap(gaps, busy_union(evs)[1])
           for name, evs in by_name.items()}
    out[none] = sum(b - a for a, b in gaps) - \
        overlap(gaps, busy_union(host_spans)[1])
    return sorted(((n, s) for n, s in out.items() if s > 0),
                  key=lambda kv: -kv[1])


def reduce(devices: Dict[str, List[Event]], t0: float, t1: float,
           host_spans: Sequence[Event] = ()) -> dict:
    """Everything the readers and the result line take from one traced
    window [t0, t1] on the trace's clock: busy seconds (averaged over
    the chips), summed device-op seconds, the top operations, and the
    idle gaps of the fullest chip by host span."""
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy, op_s = [], []
    worst = None
    for name in sorted(devices):
        events = clip(devices[name], t0, t1)
        b, merged = busy_union(events)
        busy.append(b)
        op_s.append(sum(d for _, _, d in events))
        if worst is None or b > worst[0]:
            worst = (b, merged, events)
    window = t1 - t0
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy),
        "device_op_s": sum(op_s) / len(op_s),
        "idle_share": 1.0 - (sum(busy) / len(busy)) / window,
        "device_ops": [[n, s] for n, s in op_totals(worst[2])[:10]],
        "idle_gaps": [[n, s] for n, s in label_gaps(
            idle_gaps(worst[1], t0, t1), host_spans)[:10]],
        "chips": len(devices),
    }
