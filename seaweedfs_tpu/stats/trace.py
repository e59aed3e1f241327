"""Lightweight span tracer: where the pipeline's time actually goes.

The fleet scheduler (ec/fleet.py) runs as reader pool -> fused RS
dispatch -> tagged retire -> per-volume writer lanes, four thread
families handing work to each other — a cProfile flattens that into
function totals and loses the overlap structure, which is exactly what
a perf PR needs to see. This module records *spans*: named, tagged
[t0, t0+dur) intervals per thread, with parent/child nesting inside a
thread (thread-local stack) and explicit handoff tokens across threads
(the packing thread mints a token, the writer lane opens its span under
it), exported as Chrome trace-event JSON that chrome://tracing and
Perfetto load directly.

Cost discipline: tracing is OFF by default and `span()` checks the
module flag before allocating anything — the disabled path is one
function call returning a shared no-op context manager (gated by
tests/test_perf_gates.py). Enabled spans land in a bounded ring buffer
(deque append is atomic under the GIL; no lock on the hot path), so a
forgotten-enabled tracer costs memory-bounded ring slots, never
unbounded growth.

Set SEAWEED_TRACE=1 to enable at import (how bench_profile.py turns on
tracing inside spawned server subprocesses); in-process callers use
enable()/disable(). `/debug/trace` on the metrics port serves the
Chrome JSON of everything currently in the ring.

`PhaseTimer` is the one way the pipeline layers (ec/fleet.py,
ops/rs_kernel.py) time a phase: a histogram observation that is always
taken plus a span that exists only while the ring is on.

In a process that has already loaded jax, a span recorded into the ring
is also opened as a `jax.profiler.TraceAnnotation`, so that a profiler
trace holds the host spans on its own clock beside the device's lines.
This module never imports jax itself: master and filer processes do not
load it.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

# Ring capacity: a fleet encode of 64 volumes emits a few spans per
# chunk — tens of thousands of spans for a big run. 1<<17 slots keep
# the whole run while bounding memory (~100 bytes/span -> ~13MB worst
# case).
DEFAULT_CAPACITY = 1 << 17

_enabled = bool(os.environ.get("SEAWEED_TRACE", "") not in ("", "0"))
_ring: deque = deque(maxlen=DEFAULT_CAPACITY)
_ids = itertools.count(1)      # .__next__ is atomic under the GIL
# Span ids are 64-bit and unique ACROSS processes: a per-process random
# high word (bit 62 forced so ids never collide with the small ids of a
# process that lost its randomness) ORed with the local counter. The
# cluster stitcher dedupes by span id, so two processes must never mint
# the same one.
_ID_BASE = (random.getrandbits(30) | (1 << 29)) << 33
_tls = threading.local()
_thread_names: Dict[int, str] = {}

# perf_counter -> wall-clock offset, taken once at import: the cluster
# collector exports span timestamps on the epoch timebase so spans from
# different PROCESSES line up in one stitched view (NTP-grade skew is
# acceptable at the millisecond scale these traces are read at).
EPOCH_OFFSET = time.time() - time.perf_counter()

# Cluster-trace hook (stats/cluster_trace.py): when on, spans are also
# appended to the ambient request's bounded buffer, carried across
# threads by contextvars (FanOutPool copies the context at submit).
# Kept as one module flag + one ContextVar so the fully-disabled span()
# fast path stays two attribute checks.
_cluster_enabled = False
_req_ctx: "contextvars.ContextVar[Optional[object]]" = \
    contextvars.ContextVar("seaweed_trace_req", default=None)


# jax.profiler.TraceAnnotation once this process is seen to have loaded
# jax (looked up in sys.modules, never imported from here).
_annotation = None


def _profiler_annotation():
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def next_span_id() -> int:
    """A fresh 64-bit process-unique span/trace id."""
    return _ID_BASE | next(_ids)


def request_ctx():
    """The ambient cluster-trace request context (or None)."""
    return _req_ctx.get()


def is_enabled() -> bool:
    return _enabled


def enable(capacity: Optional[int] = None) -> None:
    """Turn tracing on (optionally resizing the ring, which clears it)."""
    global _enabled, _ring
    if capacity is not None and capacity != _ring.maxlen:
        _ring = deque(maxlen=capacity)
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def clear() -> None:
    _ring.clear()
    _thread_names.clear()


class _NoopSpan:
    """Shared do-nothing context manager: the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def token(self) -> None:
        return None


NOOP = _NoopSpan()


class Span:
    __slots__ = ("name", "tags", "id", "parent_id", "t0", "dur", "tid",
                 "trace_id", "_ann")

    def __init__(self, name: str, parent: Optional[int], tags: dict):
        self.name = name
        self.tags = tags
        self.id = _ID_BASE | next(_ids)
        self.parent_id = parent
        self.t0 = 0.0
        self.dur = 0.0
        self.tid = 0
        self.trace_id = 0
        self._ann = None

    def __enter__(self) -> "Span":
        tid = threading.get_ident()
        self.tid = tid
        if tid not in _thread_names:
            _thread_names[tid] = threading.current_thread().name
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if self.parent_id is None and stack:
            self.parent_id = stack[-1]
        if _cluster_enabled:
            ctx = _req_ctx.get()
            if ctx is not None:
                self.trace_id = ctx.trace_id
                ctx.current = self.name   # flight-recorder "where is it"
                if self.parent_id is None:
                    # first span on a pool/hedge worker thread: parent
                    # to the request span across the thread boundary
                    self.parent_id = ctx.span_id
        stack.append(self.id)
        if _enabled:
            ann = _profiler_annotation()
            if ann is not None:
                self._ann = ann(self.name)
                self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] == self.id:
            stack.pop()
        if _enabled:
            _ring.append(self)
        if _cluster_enabled:
            ctx = _req_ctx.get()
            if ctx is not None:
                ctx.add_span(self)
        return False

    def token(self) -> int:
        """Handoff token: pass to span(parent=...) in another thread so
        the child nests under this span across the thread boundary."""
        return self.id


def span(name: str, parent: Optional[int] = None, **tags):
    """Context manager recording one span; no-op while disabled.

    `parent` is a handoff token from Span.token() (or handoff()) for
    cross-thread nesting; same-thread nesting is automatic. Callers on
    paths hot enough that even the kwargs dict matters should gate on
    is_enabled() themselves.

    Enabled means EITHER the local span ring (SEAWEED_TRACE) or the
    cluster tracer (stats/cluster_trace.py) is on — with both off the
    fast path is two module-flag checks returning the shared no-op.
    """
    if not _enabled and not _cluster_enabled:
        return NOOP
    return Span(name, parent, tags)


class PhaseTimer:
    """One timed phase of a pipeline layer: always observed into `hist`
    (an already-resolved histogram child: labels() takes a lock), and
    recorded as a span `name` only while the ring is on, nested under
    the calling thread's open span or under the handoff token `parent`.
    The disabled path costs two clock reads and one observe, and
    allocates no Span."""

    __slots__ = ("_hist", "_span", "_t0")

    def __init__(self, hist, name: str, parent: Optional[int] = None,
                 **tags):
        self._hist = hist
        self._span = Span(name, parent, tags) if _enabled else NOOP

    def __enter__(self) -> "PhaseTimer":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._hist.observe(time.perf_counter() - self._t0)
        return self._span.__exit__(*exc)

    def token(self) -> Optional[int]:
        """Handoff token of the underlying span (None when disabled)."""
        return self._span.token()


def active() -> bool:
    """True when span() would record anything right now — the guard
    hot callers use before building a tags dict."""
    return _enabled or (_cluster_enabled and _req_ctx.get() is not None)


def handoff() -> Optional[int]:
    """Token for the innermost open span of THIS thread (None when
    disabled or no span is open): hand it to the thread that continues
    the work so its spans parent here."""
    if not _enabled:
        return None
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


# -- export -------------------------------------------------------------------

def spans() -> List[Span]:
    """Snapshot of the ring, oldest first."""
    return list(_ring)


def chrome_trace(extra: Sequence[Span] = ()) -> dict:
    """Chrome trace-event JSON object (the 'JSON Object Format':
    {"traceEvents": [...]}), loadable by chrome://tracing / Perfetto.

    Spans become 'X' (complete) events; thread names become 'M'
    metadata events so Perfetto labels the lanes. ts/dur are in
    microseconds on the perf_counter timebase (arbitrary origin is fine
    for these viewers).
    """
    pid = os.getpid()
    events: List[dict] = []
    for tid, tname in list(_thread_names.items()):
        events.append({"ph": "M", "pid": pid, "tid": tid,
                       "name": "thread_name", "args": {"name": tname}})
    for s in list(_ring) + list(extra):
        ev = {"ph": "X", "pid": pid, "tid": s.tid, "name": s.name,
              "ts": round(s.t0 * 1e6, 3), "dur": round(s.dur * 1e6, 3)}
        args = dict(s.tags) if s.tags else {}
        args["id"] = s.id
        if s.parent_id is not None:
            args["parent"] = s.parent_id
        if s.trace_id:
            args["trace"] = f"{s.trace_id:016x}"
        ev["args"] = args
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def span_dict(s: Span) -> dict:
    """One span as the cluster collector exports it: epoch-based
    microsecond timestamps (comparable across processes), hex ids."""
    d = {"name": s.name,
         "ts_us": round((s.t0 + EPOCH_OFFSET) * 1e6, 3),
         "dur_us": round(s.dur * 1e6, 3),
         "id": f"{s.id:016x}",
         "tid": s.tid}
    if s.parent_id:
        d["parent"] = f"{s.parent_id:016x}"
    if s.trace_id:
        d["trace"] = f"{s.trace_id:016x}"
    if s.tags:
        d["tags"] = {k: str(v) for k, v in s.tags.items()}
    return d


def chrome_trace_json() -> str:
    return json.dumps(chrome_trace())


# -- rollups ------------------------------------------------------------------

def rollup(items: Optional[Sequence[Span]] = None) -> Dict[str, dict]:
    """Per-span-name totals: {name: {count, total_s, max_s}} — the
    stage-attribution summary bench.py attaches to its BENCH JSON."""
    out: Dict[str, dict] = {}
    for s in (spans() if items is None else items):
        r = out.get(s.name)
        if r is None:
            r = out[s.name] = {"count": 0, "total_s": 0.0, "max_s": 0.0}
        r["count"] += 1
        r["total_s"] += s.dur
        r["max_s"] = max(r["max_s"], s.dur)
    for r in out.values():
        r["total_s"] = round(r["total_s"], 6)
        r["max_s"] = round(r["max_s"], 6)
    return out


def busy_union_s(items: Sequence[Span], t0: float, t1: float,
                 prefixes: Optional[Sequence[str]] = None) -> float:
    """Seconds of [t0, t1] covered by at least one span (optionally
    restricted to names starting with any of `prefixes`): the coverage
    measure behind the bench --trace >=90% acceptance gate. Spans run
    on many threads, so this is interval union, not a sum."""
    ivals = []
    for s in items:
        if prefixes is not None and \
                not any(s.name.startswith(p) for p in prefixes):
            continue
        a, b = max(s.t0, t0), min(s.t0 + s.dur, t1)
        if b > a:
            ivals.append((a, b))
    ivals.sort()
    covered = 0.0
    cur_a = cur_b = None
    for a, b in ivals:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                covered += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        covered += cur_b - cur_a
    return covered
