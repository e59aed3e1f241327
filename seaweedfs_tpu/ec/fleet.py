"""Cross-volume batched EC scheduler — the fleet encoder.

`ec/encoder.py` works on ONE volume at a time: every chunk is its own
RS dispatch and a single reader thread feeds the device, so a fleet of
volumes serializes on dispatch latency and on that thread's disk reads.
This module batches chunks ACROSS volumes. Its three passes — encode
(`fleet_write_ec_files`), rebuild (`fleet_rebuild_ec_files`), verify
(`fleet_verify_ec_files`, the scrub) — are each a plan of spans and a
flush for ONE loop, `_staged_pass`:

  pack      spans from many volumes share the ten input rows of one
            [14, lanes] staging buffer — the layout the device wants —
            so 64 small volumes cost a handful of dispatches, and
            nothing is copied between the read and the placement: the
            buffer IS the dispatch's input and, in its last rows, the
            place its result lands, reused from dispatch to dispatch
            and from pass to pass (`_Staging`).
  feed      a bounded reader pool prefetches spans ahead of the device,
            each reader filling its span's lanes straight from the .dat
            (encode), the ten surviving shard files (rebuild) or the
            ten data shard files (verify; on the jax backend the
            stored parity files too, into rows 10-13: the device
            compares). Spans are consumed in
            submission order (round-robin rounds over the volumes), so
            per-volume order holds while reads overlap compute.
  dispatch  the jax backend is async already; sync host backends
            (native/numpy) are lifted to the same handle contract by a
            small encode pool, so RS compute runs multi-core and
            overlaps the reader and writer threads.
  retire    a tagged completion queue: one retire thread awaits
            dispatches strictly in submission order and hands every
            volume's output to that volume's writer LANE (per-volume
            FIFO, parallel across volumes): encoded parity and rebuilt
            shards are appended to the .ecNN files; a host codec's
            verify parity is compared with the stored .ec10-13, a jax
            verify's counts (all that came back) are added up.

Volumes that need large-row striping (> 10 * large_block bytes) fall
back to the per-volume `write_ec_files` path; everything else is
byte-identical to it (uniform small rows).

The same passes over a device mesh: `parallel/mesh_fleet.py`; one of
these schedulers per device: `parallel.fleet_write_ec_files_sharded`.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import queue
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from seaweedfs_tpu.ec import encoder as _encoder
from seaweedfs_tpu.ec.encoder import (
    LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, default_chunk_for, shard_file_name)
from seaweedfs_tpu.ops.rs_code import ReedSolomon, DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.resilience import failpoint as _failpoint
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import (
    FleetDispatchBatchHistogram, FleetDispatchedBytesCounter,
    FleetMeshFallbacksCounter, FleetPassPartSecondsHistogram,
    FleetPassSecondsHistogram, FleetReaderQueueGauge,
    FleetRebuildGroupsCounter, FleetRebuildVolumesCounter,
    FleetRebuiltBytesCounter, FleetRemoteReadSecondsHistogram,
    FleetStageSecondsHistogram, FleetStagingBuffersCounter,
    FleetVerifyBytesCounter, FleetVerifyRowBytesCounter,
    FleetWaitSecondsHistogram, FleetWriterBacklogGauge)


def mesh_fleet_or_none():
    """The pod-scale mesh scheduler module (parallel/mesh_fleet), or
    None on a jax-less host — parallel's package import needs jax at
    import time. A None return counts as a mesh fallback; the caller
    runs the host fleet path instead."""
    try:
        from seaweedfs_tpu.parallel import mesh_fleet
        return mesh_fleet
    except ImportError:
        FleetMeshFallbacksCounter.labels("unavailable").inc()
        return None

# Reader-pool width: enough to keep several volumes' sequential reads
# in flight without degrading each stream to fully random IO.
FLEET_READERS = 4

# Fused dispatches in flight at once — the writer-queue bound, same
# double-buffering role as encoder.PIPELINE_DEPTH. With the reader
# prefetch it sets a pass's share of staging buffers (see `_Staging`),
# which is a pass's peak host memory.
FLEET_DEPTH = 2

# Encode pool for synchronous host backends: ctypes/numpy release the
# GIL, so two in-flight fused encodes use two cores — the host-side
# analogue of the device's async dispatch queue.
FLEET_ENCODERS = max(2, min(4, os.cpu_count() or 2))

# Writer lanes: each volume's writes stay FIFO on one lane, but lanes
# run in parallel, so the fleet's file writes (the larger half of the
# IO: 14 bytes out per 10 in) spread across cores instead of
# serializing behind a single writer thread.
FLEET_WRITERS = max(2, min(4, os.cpu_count() or 2))

# Bound on queued writes per lane: with ~chunk-sized spans this caps
# writer-side buffering at a few spans per lane.
_LANE_QUEUE = 4


# Stage-latency children resolved once at import: labels() takes a
# lock per call, and a stage interval closes for every chunk-sized
# unit of work.
_STAGE_HIST = {s: FleetStageSecondsHistogram.labels(s)
               for s in ("read", "pack", "dispatch", "rs", "retire",
                         "write", "verify", "upload")}
_WAIT_HIST = {on: FleetWaitSecondsHistogram.labels(on)
              for on in ("reader", "retire_slot", "lane_from_pack",
                         "lane_from_retire", "staging")}
# A pass's wall and its two ends, by the kind of pass.
_PASSES = ("encode", "rebuild", "verify")
_PASS_HIST = {kind: FleetPassSecondsHistogram.labels(kind)
              for kind in _PASSES}
_PART_HIST = {(kind, part): FleetPassPartSecondsHistogram.labels(kind, part)
              for kind in _PASSES for part in ("fill", "drain")}
_STAGING_HANDED = {state: FleetStagingBuffersCounter.labels(state)
                   for state in ("fresh", "reused")}
_VERIFIED_BYTES = {where: FleetVerifyBytesCounter.labels(where)
                   for where in ("device", "host")}
_ROW_BYTES = {source: FleetVerifyRowBytesCounter.labels(source)
              for source in ("local", "remote")}
_REMOTE_READ = FleetRemoteReadSecondsHistogram.labels()


class _StageTimer(trace.PhaseTimer):
    """One pipeline-stage interval: the shared phase timer under the
    stage's histogram child and the span name `fleet.<stage>`."""

    __slots__ = ()

    def __init__(self, stage: str, parent: Optional[int] = None, **tags):
        super().__init__(_STAGE_HIST[stage], "fleet." + stage, parent,
                         **tags)


def _pass_part(kind: str, part: str) -> trace.PhaseTimer:
    """Timer of one end of a pass (span `fleet.pass.<part>`, on the
    packing thread under the pass's own span)."""
    return trace.PhaseTimer(_PART_HIST[kind, part], "fleet.pass." + part)


def _waiting(on: str) -> trace.PhaseTimer:
    """Timer around one place where a scheduler thread blocks on
    another (span `fleet.wait.<on>`)."""
    return trace.PhaseTimer(_WAIT_HIST[on], "fleet.wait." + on)


class TaggedPipeline:
    """Tagged completion queue: fused dispatches retire FIFO, writes
    fan out to per-volume writer lanes.

    One retire thread awaits dispatch handles strictly in submission
    order — the deque discipline of `encoder._EncodePipeline` — and
    routes each tagged span's parity write to `tag % lanes`. All of a
    volume's writes carry the volume's tag, so they land on ONE lane in
    enqueue order (per-volume FIFO by construction) while different
    volumes' writes proceed in parallel. Data-shard writes (`write`)
    need no handle and go straight to the lane from the packing thread;
    they interleave with parity writes on the lane but touch disjoint
    files (.ec00-09 vs .ec10-13), so only the per-file order matters —
    and each file's writes come from a single ordered source.
    """

    def __init__(self, depth: int = FLEET_DEPTH,
                 writers: int = FLEET_WRITERS):
        self._lanes: List["queue.Queue[Optional[Tuple]]"] = [
            queue.Queue(maxsize=_LANE_QUEUE)
            for _ in range(max(1, writers))]
        self._retireq: "queue.Queue[Optional[Tuple]]" = \
            queue.Queue(maxsize=max(1, depth))
        self._exc: Optional[BaseException] = None
        # per-lane backlog gauges resolved once: labels() locks per call
        self._lane_gauges = [FleetWriterBacklogGauge.labels(str(i))
                             for i in range(len(self._lanes))]
        self._writers = [
            # lint: gate-ok(TaggedPipeline is built per fleet pass: construction is first use) # lint: thread-ok(fleet writers carry explicit volume tags, not request context)
            threading.Thread(target=self._drain_lane, args=(q, i),
                             name=f"fleet-write-{i}", daemon=True)
            for i, q in enumerate(self._lanes)]
        # lint: gate-ok(TaggedPipeline is built per fleet pass: construction is first use) # lint: thread-ok(retire thread carries explicit tags, not request context)
        self._retirer = threading.Thread(
            target=self._retire_loop, name="fleet-retire", daemon=True)
        for t in self._writers:
            t.start()
        self._retirer.start()

    def _put_lane(self, tag: int, fn: Callable[[], None],
                  token: Optional[int], on: str,
                  timeout_s: Optional[float] = None) -> None:
        """`on` names the caller's thread for the wait metric:
        lane_from_pack or lane_from_retire."""
        lane = tag % len(self._lanes)
        # inc/dec deltas, not set(qsize): several schedulers run
        # concurrently (mesh sharding, parallel generate RPCs) and
        # share these children, so the gauge must SUM their backlogs
        # rather than last-write-wins one scheduler's view
        self._lane_gauges[lane].inc()
        try:
            with _waiting(on):
                self._lanes[lane].put((fn, token), timeout=timeout_s)
        except queue.Full:
            self._lane_gauges[lane].dec()  # never entered the lane
            raise

    def write(self, tag: int, fn: Callable[[], None],
              timeout_s: Optional[float] = None) -> None:
        """Enqueue one ordered write on `tag`'s lane (no handle).
        With timeout_s, a lane that stays full that long raises
        queue.Full instead of blocking the caller behind a wedged
        writer — same stall contract as submit()."""
        self._raise_pending()
        self._put_lane(tag, fn, trace.handoff(), "lane_from_pack",
                       timeout_s)

    def submit(self, handle,
               tagged: Sequence[Tuple[int, Callable]],
               timeout_s: Optional[float] = None) -> None:
        """Queue a dispatch: when `handle` resolves (FIFO), span i's
        output goes to `tagged[i] = (tag, fn)` as `fn(outs[i])` on
        tag's lane. With timeout_s, waiting `timeout_s` for a free
        in-flight slot raises queue.Full — the mesh scheduler's
        dispatch-stall detection (parallel/mesh_fleet.py) — instead of
        blocking forever behind a wedged retire."""
        self._raise_pending()
        item = (handle, list(tagged), trace.handoff())
        with _waiting("retire_slot"):
            self._retireq.put(item, timeout=timeout_s)

    def _retire_loop(self) -> None:
        while True:
            item = self._retireq.get()
            if item is None:
                return
            if self._exc is not None:
                continue  # failed: keep draining, write nothing more
            handle, tagged, token = item
            try:
                # the retire stage is where async dispatches actually
                # resolve — for the jax backend this wait IS the device
                # time (block_until_ready), for host backends the encode
                # pool's compute; the lane puts after it are writer-side
                # backpressure, also this stage's problem (told apart
                # from the device by fleet_wait_seconds{lane_from_retire}
                # and rs_dispatch_seconds{wait,fetch,unstage})
                with _StageTimer("retire", parent=token,
                                 spans=len(tagged)) as st:
                    outs = handle.result()
                    for (tag, fn), out in zip(tagged, outs):
                        self._put_lane(tag, functools.partial(fn, out),
                                       st.token(), "lane_from_retire")
            except BaseException as e:  # surfaced on submit/drain
                if self._exc is None:
                    self._exc = e

    def _drain_lane(self, q: "queue.Queue[Optional[Tuple]]",
                    lane: int) -> None:
        while True:
            item = q.get()
            if item is None:
                return
            self._lane_gauges[lane].dec()
            if self._exc is not None:
                continue
            fn, token = item
            try:
                with _StageTimer("write", parent=token, lane=lane):
                    fn()
            except BaseException as e:
                if self._exc is None:
                    self._exc = e

    def _raise_pending(self) -> None:
        # _exc stays latched once set: clearing it here would re-enable
        # the retire/writer threads after they skipped a failed span,
        # letting later spans land past a hole in the shard files
        if self._exc is not None:
            raise self._exc

    def drain(self) -> None:
        """Flush every queued write, stop all threads, re-raise the
        first error (if any). The pipeline is spent afterwards."""
        self._retireq.put(None)
        self._retirer.join()
        for q in self._lanes:
            q.put(None)
        for t in self._writers:
            t.join()
        self._raise_pending()


class _Gathered:
    """Handle over several in-flight per-span encodes: .result() is the
    list of per-span outputs, ordered like the spans were packed.
    `done` runs once every one of them has resolved."""

    def __init__(self, handles, done: Callable[[], None]):
        self._handles = handles
        self._done = done

    def result(self) -> List[np.ndarray]:
        outs = [h.result() for h in self._handles]
        self._done()
        return outs


def _rs_staged(fn, arr: np.ndarray, parent: Optional[int]) -> np.ndarray:
    """One host-backend RS compute task, attributed to the 'rs' stage
    (the jax path's device time shows up in 'retire' instead, where
    handle.result() blocks)."""
    with _StageTimer("rs", parent=parent):
        return fn(arr)


class _Dispatcher:
    """Uniform async-handle dispatch over any RS backend.

    jax dispatches are inherently async (the device computes while the
    host stages IO), so a fused batch is issued as one dispatch —
    fewer, fuller device slabs. Host backends compute synchronously
    instead, so each span goes to a small encode pool as its own task
    (the GIL-free native/numpy kernels genuinely run on other cores)
    and the handles are gathered. Either way .result() yields per-span
    output arrays, and the input is a slice of the staging buffer
    (taking it is all the `pack` stage times).
    """

    def __init__(self, rs: ReedSolomon, device=None,
                 encoders: int = FLEET_ENCODERS):
        self._rs = rs
        self._device = device
        self._pool = None
        if rs.backend != "jax":
            # lint: thread-ok(fleet dispatch pool; work items are explicit, no ambient request state)
            self._pool = ThreadPoolExecutor(
                max_workers=max(1, encoders),
                thread_name_prefix="fleet-encode")

    def room(self, lanes: int) -> int:
        """Lanes of a staging buffer that a dispatch of `lanes` takes:
        the jax dispatch layer places whole slabs, the last one padded
        up to its power-of-two width, and a buffer that reaches that
        far is sliced for every slab and never copied. Host codecs take
        the lanes as they are."""
        if self._pool is not None:
            return lanes
        from seaweedfs_tpu.ops import rs_kernel
        return rs_kernel.placed_lanes(lanes)

    def _lanes(self, op: str, rows: int, apply_async, apply,
               buf: np.ndarray, cuts: List[Tuple[int, int]],
               done: Callable[[], None]):
        """One GF map of `rows` output rows over a staging buffer
        [14, lanes] whose spans lie at `cuts` = [(lane offset, lanes)],
        back to back from lane 0: .result() yields one [rows, lanes]
        array a span. The jax branch hands the ten input rows over as
        they are, a 2-D view of the filled lanes AND the buffer's slack
        after them up to the tail slab's end (`room`: whatever an
        earlier dispatch left there pads the tail, and is trimmed off
        with it), and lends the filled lanes of the rows after them for
        the result: no copy before the dispatch layer's slab slices,
        and after its fetch one copy into memory that was touched
        before. Host backends get one pool task a span, each a view;
        their codecs allocate their own results.
        `done` runs when every read of the input rows on behalf of this
        dispatch is over (retire thread)."""
        if _failpoint._armed:
            _failpoint.hit("fleet.dispatch", op=op)
        if self._pool is None:
            with _StageTimer("pack", spans=len(cuts)):
                used = cuts[-1][0] + cuts[-1][1]
                data = buf[:DATA_SHARDS, :self.room(used)]
                out = buf[DATA_SHARDS:DATA_SHARDS + rows, :used]
            handle = apply_async(data, device=self._device, out=out,
                                 lanes=used)
            return _SplitHandle(handle, [n for _, n in cuts], done)
        token = trace.handoff()
        return _Gathered([self._pool.submit(
            _rs_staged, apply, buf[:DATA_SHARDS, off:off + n], token)
            for off, n in cuts], done)

    def encode_lanes(self, buf: np.ndarray, cuts: List[Tuple[int, int]],
                     done: Callable[[], None]):
        """Parity [4, lanes] of every span of a staging buffer."""
        return self._lanes("encode", TOTAL_SHARDS - DATA_SHARDS,
                           self._rs.encode_async, self._rs.encode, buf,
                           cuts, done)

    def reconstruct_lanes(self, present, missing, buf: np.ndarray,
                          cuts: List[Tuple[int, int]],
                          done: Callable[[], None]):
        """Shards `missing` [len(missing), lanes] of every span of a
        staging buffer whose rows are the first ten of `present`."""
        return self._lanes(
            "reconstruct", len(missing),
            functools.partial(self._rs.reconstruct_some_async, present,
                              missing),
            functools.partial(self._rs.reconstruct_some, present, missing),
            buf, cuts, done)

    def verify_lanes(self, buf: np.ndarray, cuts: List[Tuple[int, int]],
                     done: Callable[[], None]):
        """The jax backend's verify: the filled lanes of ALL 14 rows go
        to the device as they lie — the data shards and the stored
        parity, and the slack after them as in `_lanes` — and .result()
        yields a span's (counts, firsts), each [4, blocks of the span]:
        nothing is lent, what comes back is KB. Every cut starts and
        ends on a block boundary."""
        from seaweedfs_tpu.ops import rs_kernel
        if _failpoint._armed:
            _failpoint.hit("fleet.dispatch", op="verify")
        with _StageTimer("pack", spans=len(cuts)):
            used = cuts[-1][0] + cuts[-1][1]
            stripe = buf[:, :self.room(used)]
        handle = rs_kernel.verify_stripe_async(
            self._rs.matrix[DATA_SHARDS:], stripe, device=self._device,
            lanes=used)
        return _CountsHandle(
            handle, [n // rs_kernel.VERIFY_BLOCK for _, n in cuts], done)

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)


class _CountsHandle:
    """Adapt one fused compare-and-count over a staging buffer's lanes
    back to per-span outputs: (counts, firsts), each the span's `sizes`
    blocks. `done` runs once the counts are on the host: the device has
    read the buffer."""

    def __init__(self, handle, sizes: List[int], done: Callable[[], None]):
        self._handle = handle
        self._cuts = np.cumsum(sizes)[:-1]
        self._done = done

    def result(self) -> List[Tuple[np.ndarray, np.ndarray]]:
        counts, firsts = self._handle.result()
        self._done()
        return list(zip(np.split(counts, self._cuts, axis=1),
                        np.split(firsts, self._cuts, axis=1)))


class _SplitHandle:
    """Adapt one fused async handle over a staging buffer's lanes back
    to per-span outputs: views of `sizes` lanes each of the result rows
    the dispatcher lent. `done` runs once the fused result is on the
    host."""

    def __init__(self, handle, sizes: List[int], done: Callable[[], None]):
        self._handle = handle
        self._sizes = sizes
        self._done = done

    def result(self) -> List[np.ndarray]:
        out = self._handle.result()
        self._done()
        return np.split(out, np.cumsum(self._sizes)[:-1], axis=1)


class _VolState:
    __slots__ = ("base", "dat_size", "n_rows", "tag")

    def __init__(self, base: str, dat_size: int, n_rows: int, tag: int = 0):
        self.base = base
        self.dat_size = dat_size
        self.n_rows = n_rows
        self.tag = tag  # writer-lane key: all this volume's writes
        #                 share it, so they stay FIFO on one lane


def _append_rows(base: str, shard_id: int,
                 rows: Sequence[np.ndarray]) -> None:
    """Append C-contiguous row slices to one shard file: the slices go
    straight to the (buffered) file object — no ascontiguousarray /
    tobytes staging copies on the write path."""
    with open(shard_file_name(base, shard_id), "ab") as f:
        for r in rows:
            f.write(r)


def _round_robin_spans(vols: List[_VolState], span_rows: int):
    """Yield (vol, row0, rows) in rounds over the volumes: round r
    hands out rows [r*span, (r+1)*span) of every volume still alive.
    Submission order == pack order == per-volume row order."""
    pending = [(v, 0) for v in vols if v.n_rows > 0]
    while pending:
        nxt = []
        for v, row0 in pending:
            rows = min(span_rows, v.n_rows - row0)
            yield v, row0, rows
            if row0 + rows < v.n_rows:
                nxt.append((v, row0 + rows))
        pending = nxt


class _IdleStaging:
    """The process's staging buffers between passes, so that the next
    pass (the next shell command) fills memory that is already mapped
    instead of faulting in fresh pages. Buffers are kept by CAPACITY,
    [14, capacity] with ONE capacity at a time, and a pass borrows
    `[:, :lanes]` views of them: an encode pass's width is set by its
    chunk, a rebuild pass's by the largest shard it was given, and a
    server that alternates them (or rebuilds volumes of another size
    every command) must not throw its pages away each time. A pass's
    `lanes` here are what its widest dispatch TAKES of a buffer
    (`_Dispatcher.room`): on the jax backend the planned lanes and the
    slack up to the tail slab's end, at most 2 Mi lanes a row of
    address space, touched only where a tail lies. The capacity is the
    widest pass so far, rounded up to a small block — an encode pass's
    width as it is — and only grows, so odd widths cannot pile up. Only
    buffers that were filled before come here (an
    untouched np.empty is address space, not memory), at most one
    pass's share: that is all the memory the scheduler keeps resident
    while idle."""

    def __init__(self):
        self._lock = threading.Lock()
        self._capacity = 0
        self._bufs: List[np.ndarray] = []

    def take(self, lanes: int, n: int) -> Tuple[int, List[np.ndarray]]:
        """(capacity a pass of `lanes` makes its new buffers with, up to
        `n` idle buffers of that capacity)."""
        with self._lock:
            if lanes > self._capacity:  # what is idle is too narrow
                self._capacity = -(-lanes // SMALL_BLOCK_SIZE) \
                    * SMALL_BLOCK_SIZE
                self._bufs = []
            taken, self._bufs = self._bufs[:n], self._bufs[n:]
            return self._capacity, taken

    def give(self, bufs: List[np.ndarray], keep: int) -> None:
        with self._lock:
            room = max(0, keep - len(self._bufs))
            # narrower ones: a wider pass came by since they were made
            self._bufs.extend([b for b in bufs
                               if b.shape[1] == self._capacity][:room])


_IDLE_STAGING = _IdleStaging()


class _StagedBatch:
    """One staging buffer on its way through a pass: the spans planned
    into it, and how many closures still have to read it (its input
    rows or its result rows)."""

    __slots__ = ("buf", "used", "spans", "refs")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.used = 0  # lanes planned so far
        # vol, first lane, lanes of the span that go out to its files
        self.spans: List[Tuple[_VolState, int, int]] = []
        self.refs = 0


class _Staging:
    """One pass's share of staging buffers.

    A staging buffer is [TOTAL_SHARDS, lanes] uint8 — the whole stripe
    of a fused dispatch in the layout the device wants. Rows 0-9 are
    its input: the readers fill them straight from the .dat files
    (encode), the surviving shard files (rebuild) or the data shard
    files (verify), the dispatch layer places slices of them, an encode
    pass's writer lanes write the data shards out of them. Rows 10..
    are where an encode's or a rebuild's retire thread puts the result
    — the four parity rows, or the first len(missing) rows the rebuilt
    shards — and where the writer lanes read it: the parity and
    rebuilt-shard writes. A jax verify has its readers fill rows 10-13
    with the STORED parity and places all 14 rows; nothing comes back
    into the buffer. Then the buffer comes round again. A rebuild of
    two shards never touches rows 12-13: they stay address space. The
    share is what the pipeline has
    in flight anyway (see _staged_pass), so the one place a pass can
    block here — `acquire`, timed as fleet.wait.staging — blocks only
    while buffers are downstream of the packing thread, where they come
    back without its help. Passes share nothing but the idle list, so
    concurrent schedulers (one a device, parallel generate RPCs) cannot
    hold each other up.

    Buffers take turns (FIFO): what a pass touches, and the process
    then keeps, is min(share, dispatches) buffers whatever the timing.
    """

    def __init__(self, lanes: int, share: int,
                 check: Callable[[], None]):
        self._lanes = lanes
        self._share = share
        self._check = check  # raises the pipeline's latched error
        self._cond = threading.Condition()
        self._capacity, self._held = _IDLE_STAGING.take(lanes, share)
        self._handed = 0
        self._free: deque = deque()

    def acquire(self) -> np.ndarray:
        state = "reused"
        with _waiting("staging"), self._cond:
            if self._handed < self._share:
                # first round: what an earlier pass left, then new ones
                if self._handed == len(self._held):
                    self._held.append(np.empty(
                        (TOTAL_SHARDS, self._capacity), dtype=np.uint8))
                    state = "fresh"
                buf = self._held[self._handed][:, :self._lanes]
            else:
                while not self._free:
                    # woken by unref; the timeout is for a latched
                    # pipeline error, after which the closures that
                    # would release a buffer are skipped
                    self._cond.wait(0.05)
                    self._check()
                buf = self._free.popleft()
            self._handed += 1
        _STAGING_HANDED[state].inc()
        return buf

    def unref(self, batch: _StagedBatch) -> None:
        """One reader of `batch.buf` is through; the last one frees it."""
        with self._cond:
            batch.refs -= 1
            if batch.refs == 0:
                self._free.append(batch.buf)
                self._cond.notify()

    def close(self) -> None:
        """End of the pass, with every thread that could touch a buffer
        joined: what was filled goes to the idle list whatever the
        reference counts say (a pass that failed leaves them open)."""
        with self._cond:
            held = list(self._held)
        _IDLE_STAGING.give(held, self._share)


try:  # 16 is POSIX's floor; sysconf says -1 for "no fixed limit"
    _IOV_MAX = max(16, os.sysconf("SC_IOV_MAX"))
except (ValueError, OSError):
    _IOV_MAX = 16


def _preadv_full(fd: int, views: List[memoryview], offset: int) -> int:
    """Fill `views` in order from `fd` at `offset`, however the kernel
    cuts the reads; returns the bytes read, short only at EOF. Trims
    `views` in place as it goes."""
    total, i = 0, 0
    while i < len(views):
        got = os.preadv(fd, views[i:i + _IOV_MAX], offset + total)
        if got == 0:
            break
        total += got
        while i < len(views) and got >= len(views[i]):
            got -= len(views[i])
            i += 1
        if got:
            views[i] = views[i][got:]
    return total


def _read_span_into(base: str, row0: int, rows: int, row_bytes: int,
                    small_block: int, buf: np.ndarray, off: int) -> None:
    """Rows [row0, row0+rows) of one volume straight into lanes
    [off, off + rows*small) of a staging buffer: block i of row r is
    contiguous in the .dat and lands in buf[i, off + r*small : +small],
    contiguous too — the [10, lanes] layout with no copy in between.
    What lies past EOF is zeroed on EVERY use: the buffer still holds
    an earlier dispatch's bytes."""
    blocks = [buf[i, off + r * small_block:off + (r + 1) * small_block]
              for r in range(rows) for i in range(DATA_SHARDS)]
    fd = os.open(base + ".dat", os.O_RDONLY)
    try:
        got = _preadv_full(fd, [memoryview(b) for b in blocks],
                           row0 * row_bytes)
    finally:
        os.close(fd)
    first, part = divmod(got, small_block)
    if first < len(blocks):
        blocks[first][part:] = 0
        for b in blocks[first + 1:]:
            b[:] = 0


def _read_staged(read, base: str, buf: np.ndarray, off: int,
                 parent: Optional[int]) -> None:
    """One planned span's `read(buf, off)` on a reader-pool thread,
    attributed to the 'read' stage and parented to the scheduler's root
    span."""
    with _StageTimer("read", parent=parent, vol=os.path.basename(base)):
        read(buf, off)


def _write_data_shards(base: str, arr: np.ndarray,
                       done: Callable[[], None]) -> None:
    """One span's lanes [10, n] of a staging buffer: row i is the next
    n bytes of data shard i, one contiguous write each. `done` tells
    the buffer that this reader of it is through."""
    for i in range(DATA_SHARDS):
        _append_rows(base, i, [arr[i]])
    done()


def _then_release(fn: Callable[[np.ndarray], None],
                  release: Callable[[], None]) -> Callable:
    """`fn(out)` for a writer lane, `out` one span's lanes of a staging
    buffer's result rows: the buffer is told that this reader of it is
    through once fn has run, also when it raised."""
    def run(out: np.ndarray) -> None:
        try:
            fn(out)
        finally:
            release()
    return run


def _write_parity_span(base: str, seg: np.ndarray) -> None:
    """One span's parity [4, n] -> append to .ec10-.ec13."""
    for p in range(seg.shape[0]):
        _append_rows(base, DATA_SHARDS + p, [seg[p]])


def fleet_write_ec_files(base_names: Sequence[str], backend: str = "auto",
                         large_block: int = LARGE_BLOCK_SIZE,
                         small_block: int = SMALL_BLOCK_SIZE,
                         chunk: Optional[int] = None,
                         readers: int = FLEET_READERS,
                         depth: int = FLEET_DEPTH,
                         encoders: int = FLEET_ENCODERS,
                         device=None) -> None:
    """Generate .ec00-.ec13 for MANY volumes, fusing chunks across
    volumes into shared RS dispatches.

    Byte-identical to running `write_ec_files` per volume: small-row
    volumes ride the fused scheduler; oversized ones (large-row
    striping) fall back to the per-volume path. `device` pins the jax
    dispatches of this scheduler to one chip (see
    parallel.fleet_write_ec_files_sharded).
    """
    if chunk is None:
        chunk = default_chunk_for(backend)
    fleet: List[str] = []
    for base in base_names:
        if os.path.getsize(base + ".dat") > DATA_SHARDS * large_block:
            _encoder.write_ec_files(base, backend=backend,
                                    large_block=large_block,
                                    small_block=small_block, chunk=chunk)
        else:
            fleet.append(base)
    if not fleet:
        return
    row_bytes = DATA_SHARDS * small_block
    vols = []
    # creating/truncating 14 output files per volume is real write-side
    # IO (measured ~10% of a small fleet's wall time), so it carries
    # the write stage's span/metric attribution
    with _StageTimer("write", setup=len(fleet)):
        for tag, base in enumerate(fleet):
            size = os.path.getsize(base + ".dat")
            vols.append(_VolState(base, size, -(-size // row_bytes), tag))
            for i in range(TOTAL_SHARDS):  # create/truncate all 14 outputs
                open(shard_file_name(base, i), "wb").close()
    alive = [v for v in vols if v.n_rows > 0]
    if not alive:
        return  # all empty: 14 empty shard files each, same as serial
    # One fused dispatch ≈ `chunk` bytes of data rows — one staging
    # buffer of batch_rows rows; span size is the per-volume slice of
    # it, so a full round across the fleet packs into one dispatch (a
    # single volume degrades to the serial shape).
    batch_rows = max(1, chunk // row_bytes)
    span_rows = max(1, batch_rows // len(alive))

    def plan():
        for v, row0, rows in _round_robin_spans(alive, span_rows):
            n = rows * small_block
            yield v, n, n, functools.partial(
                _read_span_into, v.base, row0, rows, row_bytes, small_block)

    def flush(batch: _StagedBatch, dispatcher: _Dispatcher,
              pipe: TaggedPipeline, release: Callable[[], None]) -> None:
        with _StageTimer("dispatch", batch=len(batch.spans)):
            handle = dispatcher.encode_lanes(
                batch.buf, [(off, n) for _, off, n in batch.spans], release)
        # data shards need no parity: straight to each volume's lane
        # (enqueued here, in pack order, so per-volume FIFO holds)
        for v, off, n in batch.spans:
            pipe.write(v.tag, functools.partial(
                _write_data_shards, v.base,
                batch.buf[:DATA_SHARDS, off:off + n], release))
        pipe.submit(handle, [
            (v.tag, _then_release(
                functools.partial(_write_parity_span, v.base), release))
            for v, _, _ in batch.spans])

    # a buffer is free again when the retire thread has the dispatch's
    # result (every transfer out of it is over) AND each span's
    # data-shard write and parity write have run on its lane
    _staged_pass("encode", dict(volumes=len(alive), backend=backend),
                 backend, device, encoders, readers, depth,
                 lanes=batch_rows * small_block,
                 per_buffer=batch_rows // span_rows, plan=plan(),
                 flush=flush, refs=lambda batch: 1 + 2 * len(batch.spans))


def _staged_pass(kind: str, tags: dict, backend: str, device,
                 encoders: int, readers: int, depth: int, *, lanes: int,
                 per_buffer: int, plan, flush,
                 refs: Callable[[_StagedBatch], int]) -> None:
    """The loop an encode, a rebuild and a verify pass share (`kind`),
    timed as a whole under the span `fleet.<kind>` with `tags`: plan
    spans into staging buffers of `lanes`, have the reader pool fill
    them ahead of the device, dispatch a buffer when its last span is
    read, retire through a TaggedPipeline.

    The pass's timer opens before its threads and buffers are made and
    closes after they are gone, so that what a pass costs to set up and
    tear down is inside its wall. Two parts of it are timed on the
    packing thread: `fill`, up to the first flush (nothing downstream
    of the readers has work yet), and `drain`, from the last flush's
    return (upstream has nothing left). What lies between is the steady
    part, where the busiest stage sets the pace.

    `plan` yields (vol, width, n, read) in submission order: the span
    takes `width` lanes of a buffer, `n` of them go out to the volume's
    files, and `read(buf, off)` fills them on a reader thread ('read').
    A span that does not fit what is left of a buffer starts the next
    one; `per_buffer` is how many full spans a buffer holds (never
    fewer). `flush(batch, dispatcher, pipe, release)` issues a complete
    batch's dispatch and queues its writes; `refs(batch)` is how many
    times what it queues will call `release`, the last of which frees
    the buffer."""
    with (trace.PhaseTimer(_PASS_HIST[kind], "fleet." + kind, **tags) as root,
          contextlib.ExitStack() as filling_part):
        # closed by the first flush, or by a pass that never got there
        filling_part.enter_context(_pass_part(kind, "fill"))
        dispatcher = _Dispatcher(ReedSolomon(backend=backend), device=device,
                                 encoders=encoders)
        # lint: thread-ok(per-pass reader pool; work items are explicit, no ambient request state)
        pool = ThreadPoolExecutor(max_workers=max(1, readers),
                                  thread_name_prefix="fleet-read")
        pipe = TaggedPipeline(depth=depth)
        prefetch = max(readers, 2 * per_buffer)
        # The pass's share of staging buffers is what the pipeline holds
        # at once: upstream of the dispatch the prefetched spans' buffers
        # (one more when they straddle), downstream `depth` queued
        # dispatches, the one in the retire thread's hand, whose result
        # is being copied into its last rows, and the one before it,
        # whose result the writer lanes are reading (lanes that fall
        # further behind than one dispatch hold the pass up here, as
        # fleet.wait.staging, where it would next wait for a lane).
        # Upstream never needs them all, so a pass out of buffers always
        # has some coming back.
        # A buffer is as wide as the dispatch layer takes for a dispatch
        # of `lanes`; the plan fills `lanes` of it.
        staging = _Staging(dispatcher.room(lanes),
                           -(-prefetch // per_buffer) + 1 + depth + 1 + 1,
                           pipe._raise_pending)
        inflight: deque = deque()
        filling: Optional[_StagedBatch] = None
        token = root.token()

        def fill() -> None:
            nonlocal filling
            while len(inflight) < prefetch:
                nxt = next(plan, None)
                if nxt is None:
                    break
                v, width, n, read = nxt
                if filling is None or filling.used + width > lanes:
                    filling = _StagedBatch(staging.acquire())
                off = filling.used
                filling.used += width
                filling.spans.append((v, off, n))
                inflight.append((filling, pool.submit(
                    _read_staged, read, v.base, filling.buf, off, token)))
                # inc/dec deltas so concurrent schedulers SUM on the
                # shared gauge instead of overwriting each other's depth
                FleetReaderQueueGauge.inc()

        try:
            fill()
            while inflight:
                batch, fut = inflight.popleft()
                FleetReaderQueueGauge.dec()
                with _waiting("reader"):
                    fut.result()
                fill()
                # spans are planned in order, so a batch is complete when
                # the next span read belongs to another (or none is left)
                if not inflight or inflight[0][0] is not batch:
                    filling_part.close()
                    batch.refs = refs(batch)
                    flush(batch, dispatcher, pipe,
                          functools.partial(staging.unref, batch))
                    FleetDispatchBatchHistogram.observe(len(batch.spans))
                    FleetDispatchedBytesCounter.inc(
                        float(DATA_SHARDS * batch.used))
        finally:
            filling_part.close()
            with _pass_part(kind, "drain"):
                # error path leftovers
                FleetReaderQueueGauge.dec(len(inflight))
                pool.shutdown(wait=True)
                try:
                    pipe.drain()  # may re-raise the latched pipeline error
                finally:
                    dispatcher.close()
                    staging.close()


# --- fleet rebuild -----------------------------------------------------------

def fleet_rebuild_ec_files(base_names: Sequence[str], backend: str = "auto",
                           chunk: Optional[int] = None,
                           wanted: Optional[List[int]] = None,
                           readers: int = FLEET_READERS,
                           depth: int = FLEET_DEPTH,
                           encoders: int = FLEET_ENCODERS,
                           device=None,
                           remote: Optional[Dict[str, RemoteRows]] = None
                           ) -> Dict[str, List[int]]:
    """Cross-volume batched `rebuild_ec_files`.

    Volumes sharing a (present, missing) signature share one decode
    matrix, so their shard chunks lie side by side in one staging
    buffer and fuse into single [10, B * span] reconstruct dispatches —
    the rebuild-side twin of `fleet_write_ec_files`, on the same loop
    and the same reused buffers. Tail spans are zero-padded to the span
    width and trimmed on writeback. Returns
    {base_name: rebuilt shard ids} (empty list where nothing was
    missing).

    `remote` (with `wanted`): for a volume with fewer than ten shard
    files here, the rows other servers hold; the fewest of them that
    make ten survivors are pulled from their holders, span by span.
    """
    if chunk is None:
        chunk = default_chunk_for(backend)
    rebuilt: Dict[str, List[int]] = {}
    groups: Dict[Tuple[Tuple[int, ...], Tuple[int, ...]],
                 List[Tuple[str, int]]] = {}
    pulled: Dict[str, RemoteRows] = {}
    for base in base_names:
        present = [i for i in range(TOTAL_SHARDS)
                   if os.path.exists(shard_file_name(base, i))]
        missing = [i for i in
                   (range(TOTAL_SHARDS) if wanted is None else wanted)
                   if i not in present]
        rebuilt[base] = missing
        if not missing:
            continue
        far = (remote or {}).get(base)
        if far is not None and len(present) < DATA_SHARDS:
            pull = [s for s in sorted(far.sids)
                    if s not in present and s not in missing]
            pull = pull[:DATA_SHARDS - len(present)]
            pulled[base] = far = RemoteRows(far.vid, frozenset(pull),
                                            far.shard_size, far.fill)
            present = sorted(present + pull)
        if len(present) < DATA_SHARDS:
            raise ValueError(
                f"cannot rebuild {base}: only {len(present)} shards present")
        shard_size = _shard_size(base, present[0], pulled.get(base))
        groups.setdefault((tuple(present), tuple(missing)),
                          []).append((base, shard_size))
    for (present, missing), members in groups.items():
        with _unlinked_on_failure([shard_file_name(base, sid)
                                   for base, _ in members
                                   for sid in missing]):
            _fleet_rebuild_group(list(present), list(missing), members,
                                 backend, chunk, readers, depth, encoders,
                                 device, len(groups), pulled)
        FleetRebuildGroupsCounter.inc()
        FleetRebuildVolumesCounter.inc(float(len(members)))
    return rebuilt


@contextlib.contextmanager
def _unlinked_on_failure(paths: List[str]):
    """A pass that fails half way leaves none of its output files
    behind: a later pass, or a mount, would take a short shard file for
    a whole one."""
    try:
        yield
    except BaseException:
        for path in paths:
            with contextlib.suppress(FileNotFoundError):
                os.remove(path)
        raise


def _stacked_spans(chunk: int, shard_sizes: Sequence[int]) -> Tuple[int, int]:
    """(span, per_batch) of a rebuild or verify pass: the width of one
    volume's span, in bytes of ONE shard row, and how many spans lie
    side by side in one [10, B * span] dispatch. `chunk` means what it
    means in `fleet_write_ec_files`: the input bytes of ALL ten rows of
    one fused dispatch. A span is every volume's equal share of a chunk,
    but no narrower than a small block: a span costs ten opens and
    reads whatever its width, so a group of 128 volumes puts 12 spans
    of 1 MiB in a dispatch, not 128 of 100 KiB. It is at most the largest
    shard (small volumes must not read and compute chunk-sized slabs of
    zero padding per 100KB shard), and the largest shard is cut into
    EQUAL spans: a last span of mostly padding costs the packing thread
    a full dispatch (5.6 % of a pass over 103 MiB shards in spans of
    6.4 MiB: 17 dispatches for the work of 16.1)."""
    largest = max(1, max(shard_sizes))
    row = max(1, chunk // DATA_SHARDS)
    widest = min(max(row // len(shard_sizes), SMALL_BLOCK_SIZE), row,
                 largest)
    span = -(-largest // -(-largest // widest))
    return span, max(1, min(len(shard_sizes), row // span))


def _write_rebuilt_span(base: str, missing: List[int], valid: int,
                        out: np.ndarray) -> None:
    """One span's rebuilt shards [len(missing), span] -> append the
    valid prefix of each row to its .ecNN file."""
    for row, sid in enumerate(missing):
        _append_rows(base, sid, [out[row, :valid]])
    FleetRebuiltBytesCounter.inc(float(len(missing) * valid))


def _read_present_span_into(base: str, present: List[int], shard_size: int,
                            offset: int, span: int, buf: np.ndarray,
                            off: int) -> None:
    """Bytes [offset, offset + span) of the first 10 present shards
    straight into lanes [off, off + span) of a staging buffer, row r
    from shard present[r] (see _read_rows_into)."""
    _read_rows_into(base, list(enumerate(present[:DATA_SHARDS])), shard_size,
                    offset, span, span, buf, off)


def _read_rows_into(base: str, rows: Sequence[Tuple[int, int]],
                    shard_size: int, offset: int, span: int, width: int,
                    buf: np.ndarray, off: int) -> None:
    """Bytes [offset, offset + span) of shard `sid` straight into lanes
    [off, off + span) of row `row` of a staging buffer, for each (row,
    sid) of `rows`: one read a shard file, nothing in between. The span
    owns `width` >= span lanes. What lies past the shard's end, past a
    file that is shorter than it should be, or past the span, is zeroed
    on EVERY use: the buffer still holds an earlier dispatch's bytes,
    possibly another volume's. (The map works column by column and the
    columns past the end are trimmed on the way out, or are zeros in
    every row and verify as such, so this keeps a dispatch's input a
    function of the files alone, and a short survivor reading as
    zeros.)"""
    want = min(span, max(shard_size - offset, 0))
    for row, sid in rows:
        lanes = buf[row, off:off + width]
        got = 0
        if want > 0:
            fd = os.open(shard_file_name(base, sid), os.O_RDONLY)
            try:
                got = _preadv_full(fd, [memoryview(lanes[:want])], offset)
            finally:
                os.close(fd)
        lanes[got:] = 0


@dataclass(frozen=True)
class RemoteRows:
    """The rows of one EC volume that a pass reads from the servers
    that hold them, not from this server's files: shards `sids`, each
    `shard_size` bytes. `fill(sid, offset, view)` places shard `sid`'s
    bytes from `offset` on in `view` (from a holder's VolumeEcShardRead
    stream) and returns how many it placed, fewer only where the
    holder's shard ends; it raises OSError when no holder gives them."""

    vid: int
    sids: FrozenSet[int]
    shard_size: int
    fill: Callable[[int, int, memoryview], int]


def _fill_remote_rows(remote: RemoteRows, rows: Sequence[Tuple[int, int]],
                      shard_size: int, offset: int, span: int, width: int,
                      buf: np.ndarray, off: int) -> None:
    """_read_rows_into for rows of shards other servers hold: each row's
    lanes filled from its holder, the rest zeroed as there. Timed a row
    at a time (span `fleet.read.remote`, inside the reader's
    `fleet.read`)."""
    want = min(span, max(shard_size - offset, 0))
    for row, sid in rows:
        lanes = buf[row, off:off + width]
        got = 0
        if want > 0:
            with trace.PhaseTimer(_REMOTE_READ,
                                  "fleet.read.remote", vid=remote.vid,
                                  shard=sid, bytes=want):
                got = remote.fill(sid, offset, memoryview(lanes[:want]))
        lanes[got:] = 0


def _read_mixed_rows(base: str, rows: Sequence[Tuple[int, int]],
                     shard_size: int, offset: int, span: int, width: int,
                     buf: np.ndarray, off: int, remote: RemoteRows) -> None:
    """One span's rows, each from this server's shard file or, for the
    shards in `remote`, from their holder."""
    _read_rows_into(base, [(r, s) for r, s in rows if s not in remote.sids],
                    shard_size, offset, span, width, buf, off)
    _fill_remote_rows(remote, [(r, s) for r, s in rows if s in remote.sids],
                      shard_size, offset, span, width, buf, off)


def _shard_size(base: str, sid: int, remote: Optional[RemoteRows]) -> int:
    if remote is not None and sid in remote.sids:
        return remote.shard_size
    return os.path.getsize(shard_file_name(base, sid))


def _fleet_rebuild_group(present: List[int], missing: List[int],
                         members: List[Tuple[str, int]], backend: str,
                         chunk: int, readers: int, depth: int,
                         encoders: int, device, groups: int,
                         pulled: Dict[str, RemoteRows]) -> None:
    # creating the output files is write-side IO, timed as in encode
    with _StageTimer("write", setup=len(members)):
        for base, _ in members:
            for sid in missing:
                open(shard_file_name(base, sid), "wb").close()
    # Uniform span width, per_batch spans side by side in a staging
    # buffer: one [10, per_batch * span] dispatch of ~chunk input bytes,
    # an encode dispatch's size.
    span, per_batch = _stacked_spans(chunk, [size for _, size in members])
    vols = [_VolState(base, size, -(-size // span), tag)
            for tag, (base, size) in enumerate(members)]

    def plan():
        for v, row0, _rows in _round_robin_spans(vols, 1):
            offset = row0 * span
            far = pulled.get(v.base)
            read = functools.partial(
                _read_present_span_into, v.base, present, v.dat_size,
                offset, span) if far is None else functools.partial(
                _read_mixed_rows, v.base,
                list(enumerate(present[:DATA_SHARDS])), v.dat_size, offset,
                span, span, remote=far)
            yield v, span, min(span, v.dat_size - offset), read

    def flush(batch: _StagedBatch, dispatcher: _Dispatcher,
              pipe: TaggedPipeline, release: Callable[[], None]) -> None:
        with _StageTimer("dispatch", batch=len(batch.spans)):
            handle = dispatcher.reconstruct_lanes(
                present, missing, batch.buf,
                [(off, span) for _, off, _ in batch.spans], release)
        pipe.submit(handle, [
            (v.tag, _then_release(
                functools.partial(_write_rebuilt_span, v.base, missing,
                                  valid), release))
            for v, _, valid in batch.spans])

    # a buffer is free again when the retire thread has the dispatch's
    # result AND each span's rebuilt shards are written out of it
    _staged_pass("rebuild", dict(volumes=len(members), backend=backend,
                                 groups=groups, present=present,
                                 missing=missing),
                 backend, device, encoders, readers, depth,
                 lanes=per_batch * span, per_buffer=per_batch, plan=plan(),
                 flush=flush, refs=lambda batch: 1 + len(batch.spans))


# --- fleet verify ------------------------------------------------------------

@dataclass
class VerifyResult:
    """Outcome of verifying one volume's EC files.

    parity_mismatch maps a parity shard id (10..13) to its count of
    bytes that differ from the re-encoded parity; first_mismatch holds
    the first differing shard offset per shard. `missing` lists shard
    files absent on disk — those are known damage (the rebuild path's
    job), not verification subjects. A volume with any data shard
    missing cannot be re-encoded and is reported with verified=False,
    as is one a remote row of which no holder gave.
    """

    parity_mismatch: Dict[int, int] = field(default_factory=dict)
    first_mismatch: Dict[int, int] = field(default_factory=dict)
    missing: List[int] = field(default_factory=list)
    parity_checked: List[int] = field(default_factory=list)
    bytes_verified: int = 0
    spans: int = 0
    verified: bool = True

    @property
    def clean(self) -> bool:
        return self.verified and not (self.parity_mismatch or self.missing)


def fleet_verify_ec_files(base_names: Sequence[str], backend: str = "auto",
                          chunk: Optional[int] = None,
                          readers: int = FLEET_READERS,
                          depth: int = FLEET_DEPTH,
                          encoders: int = FLEET_ENCODERS,
                          device=None,
                          throttler=None,
                          on_span: Optional[Callable[
                              [str, int, int, np.ndarray], None]] = None,
                          remote: Optional[Dict[str, RemoteRows]] = None
                          ) -> Dict[str, "VerifyResult"]:
    """Verify EC stripe consistency for MANY volumes in one fused pass.

    The scrub scanner's compute path, on the encode and rebuild passes'
    loop. The backend says where the re-encode-and-compare runs. jax:
    the readers fill ALL 14 rows of the reused staging buffers — the
    data shards and, beside them, the stored .ec10-13 — a dispatch
    places the stripe as it lies and the device sends back counts
    (rs_kernel.verify_stripe_async): no parity crosses to the host.
    Host codecs: the data rows are re-encoded in shared [10, B * span]
    dispatches and each span's parity is compared with the parity files
    on its volume's writer lane. Nothing on disk is touched; mismatches
    are reported per parity shard for the repair planner to classify (a
    corrupt DATA shard surfaces as all four parity shards disagreeing at
    the same offsets — scrub/planner.py). `throttler`
    (util.throttler.Throttler) paces the read side so a background
    scrub stays inside its IO budget. `on_span(base, offset, valid,
    rows)`, when given, is called on the volume's writer lane after each
    span's compare, in offset order: rows [10, valid] are the span's
    data-shard bytes at shard offsets [offset, offset + valid) as the
    readers put them in the staging buffer (the scrub checks its
    needles there instead of reading them again); the buffer is held
    until every span's call has returned, as an encode's is for its
    writes. Without it the buffer is free once the dispatch has read it.

    `remote`: for a volume whose shards are spread over servers, the
    rows of the shards it has no file of here (RemoteRows): the readers
    fill those rows of the same lanes from the holders, and the rest of
    the pass is as for a volume whose shards are all here. A remote row
    that comes back short reads as zeros past its end, as a short file
    does on the device. A host codec compares a remote parity row in
    the staging buffer, which is then held for the volume's lanes too.
    A remote row that no holder gives (`fill` raises OSError) declines
    its volume alone, with no evidence kept (verified=False, as for a
    missing data shard): its later spans pull nothing and reach neither
    the compare nor on_span, and the other volumes' pass goes on.
    """
    if chunk is None:
        chunk = default_chunk_for(backend)
    on_device = backend == "jax"
    results: Dict[str, VerifyResult] = {}
    sized: List[Tuple[str, int]] = []  # (base, shard size) to verify
    short: List[str] = []
    spread: Dict[str, RemoteRows] = {}  # the remote rows a pass reads
    unreached: set = set()  # volumes a remote row of which no holder gave
    for base in base_names:
        r = results[base] = VerifyResult()
        present = [i for i in range(TOTAL_SHARDS)
                   if os.path.exists(shard_file_name(base, i))]
        far = (remote or {}).get(base)
        if far is not None:
            far = RemoteRows(far.vid, frozenset(far.sids - set(present)),
                             far.shard_size, far.fill)
            present = sorted(set(present) | far.sids)
        r.missing = [i for i in range(TOTAL_SHARDS) if i not in present]
        parity = [i for i in present if i >= DATA_SHARDS]
        if any(i < DATA_SHARDS for i in r.missing) or not parity:
            # can't re-encode without every data shard (or compare
            # without any parity): known damage, rebuild's job
            r.verified = False
            continue
        r.parity_checked = parity
        if far is not None and far.sids:
            spread[base] = far
        size = _shard_size(base, 0, far)
        if on_device and any(
                os.path.getsize(shard_file_name(base, sid)) < size
                for sid in parity if far is None or sid not in far.sids):
            short.append(base)
        else:
            sized.append((base, size))
    if short:
        # A parity file that ends early lacks bytes the data shards say
        # should exist, and each of them is a mismatch whatever the
        # re-encode gives there. The device sees zeros where the file
        # has nothing and would count only those that re-encode to
        # non-zero: such a volume (damaged already, the rebuild's next)
        # is held to its files by a host codec, which is exact.
        results.update(fleet_verify_ec_files(
            short, readers=readers, depth=depth, encoders=encoders,
            throttler=throttler, remote=remote))
    if not any(size for _, size in sized):
        return results  # nothing to read: no pool, no buffer
    span, per_batch = _stacked_spans(chunk, [size for _, size in sized])
    # What a span takes of a buffer: from the device a count comes back
    # a block of lanes, so every span starts on a block boundary there
    # (the reader zeroes the lanes past the span: zeros verify).
    block = 1
    if on_device:
        from seaweedfs_tpu.ops.rs_kernel import VERIFY_BLOCK as block
    width = -(-span // block) * block
    vols = [_VolState(base, size, -(-size // span), tag)
            for tag, (base, size) in enumerate(sized)]
    where = _VERIFIED_BYTES["device" if on_device else "host"]

    def far_parity(v: _VolState) -> List[int]:
        """The parity rows a host codec compares in the buffer."""
        far = spread.get(v.base)
        return [] if on_device or far is None else \
            [sid for sid in results[v.base].parity_checked
             if sid in far.sids]

    def plan():
        for v, row0, _rows in _round_robin_spans(vols, 1):
            offset = row0 * span
            parity = results[v.base].parity_checked
            if throttler is not None:
                # paced on the packing thread, which pulls the plan: a
                # span costs 10 data reads plus its parity reads
                throttler.maybe_slowdown(span * (DATA_SHARDS + len(parity)))
            # the device is handed the stored parity beside the data
            shards = list(range(DATA_SHARDS)) + \
                (parity if on_device else far_parity(v))
            rows = [(sid, sid) for sid in shards]
            valid = min(span, v.dat_size - offset)
            far = spread.get(v.base)
            if far is None:
                _ROW_BYTES["local"].inc(float(valid * len(rows)))
                read = functools.partial(_read_rows_into, v.base, rows,
                                         v.dat_size, offset, span, width)
            else:
                pulled = sum(sid in far.sids for sid in shards)
                _ROW_BYTES["local"].inc(float(valid * (len(rows) - pulled)))
                _ROW_BYTES["remote"].inc(float(valid * pulled))
                read = functools.partial(read_spread, v.base, rows,
                                         v.dat_size, offset, far)
            yield v, width, valid, read

    def read_spread(base: str, rows, size: int, offset: int,
                    far: RemoteRows, buf: np.ndarray, off: int) -> None:
        """A span of a volume with remote rows; one that no holder gives
        declines the volume: its lanes are zeroed, and pulled no more."""
        if base not in unreached:
            try:
                _read_mixed_rows(base, rows, size, offset, span, width,
                                 buf, off, remote=far)
                return
            except OSError:
                unreached.add(base)
        buf[:, off:off + width] = 0

    def tally(v: _VolState, sid: int, offset: int, bad: int,
              first: int) -> None:
        if bad:
            r = results[v.base]
            r.parity_mismatch[sid] = r.parity_mismatch.get(sid, 0) + bad
            # spans retire in offset order on this volume's lane: the
            # first recorded hit is the lowest
            r.first_mismatch.setdefault(sid, offset + first)

    def verified(v: _VolState, valid: int) -> None:
        r = results[v.base]
        r.bytes_verified += DATA_SHARDS * valid
        r.spans += 1
        where.inc(float(DATA_SHARDS * valid))

    def counted(v: _VolState, offset: int, out) -> None:
        """On v's writer lane: the device's counts of one span, each
        [4, blocks]."""
        if v.base in unreached:
            return
        with _StageTimer("verify", vol=os.path.basename(v.base)):
            counts, firsts = out
            for sid in results[v.base].parity_checked:
                row = counts[sid - DATA_SHARDS]
                hit = np.flatnonzero(row)
                if len(hit):
                    tally(v, sid, offset, int(row.sum()),
                          int(hit[0]) * block
                          + int(firsts[sid - DATA_SHARDS, hit[0]]))
            verified(v, min(span, v.dat_size - offset))

    # open for the whole pass: a volume's compares run FIFO on ITS
    # writer lane (one reader a file), and per-span open/close would cost
    # thousands of syscalls per volume once large fleets shrink the span
    parity_files: Dict[Tuple[str, int], object] = {}

    def compare(v: _VolState, offset: int, out: np.ndarray,
                stripe: Optional[np.ndarray] = None) -> None:
        """On v's writer lane: a host codec's parity [4, span] against
        the stored one, read from its file or, for a remote row, from
        the span's `stripe` of the staging buffer."""
        if v.base in unreached:
            return
        with _StageTimer("verify", vol=os.path.basename(v.base)):
            valid = min(span, v.dat_size - offset)
            for sid in results[v.base].parity_checked:
                f = parity_files.get((v.base, sid))
                if f is None:
                    stored = stripe[sid, :valid]
                else:
                    f.seek(offset)
                    stored = np.frombuffer(f.read(valid), dtype=np.uint8)
                diff = np.nonzero(
                    out[sid - DATA_SHARDS, :len(stored)] != stored)[0]
                # a truncated parity shard lacks bytes the data shards say
                # should exist: each is a mismatch, not a free pass
                tally(v, sid, offset, len(diff) + valid - len(stored),
                      int(diff[0]) if len(diff) else len(stored))
            verified(v, valid)

    check = counted if on_device else compare

    def holds(v: _VolState) -> bool:
        """Whether v's lane closures read the staging buffer."""
        return on_span is not None or bool(far_parity(v))

    def handed(v: _VolState, offset: int, stripe: np.ndarray, out) -> None:
        """On v's writer lane: the span's compare, then its data rows
        to on_span."""
        if v.base in unreached:
            return
        if on_device:
            counted(v, offset, out)
        else:
            compare(v, offset, out, stripe)
        if on_span is not None:
            on_span(v.base, offset, stripe.shape[1], stripe[:DATA_SHARDS])

    # batches are flushed in the plan's order: a volume's spans in turn
    offsets = [itertools.count(0, span) for _ in vols]

    def flush(batch: _StagedBatch, dispatcher: _Dispatcher,
              pipe: TaggedPipeline, release: Callable[[], None]) -> None:
        cuts = [(off, width) for _, off, _ in batch.spans]
        with _StageTimer("dispatch", batch=len(batch.spans)):
            handle = dispatcher.verify_lanes(batch.buf, cuts, release) \
                if on_device else \
                dispatcher.encode_lanes(batch.buf, cuts, release)
        pipe.submit(handle, [
            (v.tag, _then_release(functools.partial(
                handed, v, next(offsets[v.tag]),
                batch.buf[:, off:off + valid]), release)) if holds(v) else
            (v.tag, functools.partial(check, v, next(offsets[v.tag])))
            for v, off, valid in batch.spans])

    with contextlib.ExitStack() as opened:
        if not on_device:
            for v in vols:  # one by one: a failed open() closes the earlier
                for sid in results[v.base].parity_checked:
                    if sid in far_parity(v):
                        continue
                    parity_files[v.base, sid] = opened.enter_context(
                        open(shard_file_name(v.base, sid), "rb"))
        # a verify's buffer is free once its dispatch's input has been
        # read (the retire thread has the counts, or every host codec's
        # parity): what the lanes then read is not in it — unless each
        # span's data rows go to on_span there, after the compare, or a
        # host codec compares a remote parity row there
        _staged_pass("verify", dict(volumes=len(vols), backend=backend),
                     backend, device, encoders, readers, depth,
                     lanes=per_batch * width, per_buffer=per_batch,
                     plan=plan(), flush=flush,
                     refs=lambda batch: 1 + sum(
                         holds(v) for v, _, _ in batch.spans))
    for base in unreached:
        results[base] = VerifyResult(missing=results[base].missing,
                                     verified=False)
    return results
