"""Pod-scale mesh data plane: ONE scheduler feeds every chip.

`fleet_write_ec_files_sharded` (parallel/mesh.py) scales the fleet by
running N INDEPENDENT schedulers, one per device: N reader pools, N
dispatch windows, N copies of writer/retire machinery, and an LPT deal
that still leaves a size-skewed tail idling chips. This module replaces
that workaround with the shape ROADMAP item 2 (and all three
SNIPPETS.md excerpts) call for — a single scheduler whose fused
``[B, 10, span]`` buckets are sharded over the whole mesh:

  geometry  every bucket has ONE fixed shape: B = dp spans (possibly
            from the same volume), span lanes padded to a multiple of
            sp; tails are zero-padded (GF maps send 0 to 0), so each
            op kind compiles exactly once per mesh.
  sharding  buckets ride ``NamedSharding(mesh, P('dp', None, 'sp'))``
            — the `_sharded_encode_fn` layout — with the GF(2) bit
            matrix replicated; the einsum contracts only the
            replicated shard axis, so dispatches insert no collectives.
  transfer  ``jax.device_put`` uploads bucket k+1 with the batch
            sharding (each chip receives only its slab; buffers are
            donated to the jit on non-host platforms) while bucket k
            computes and bucket k-1's writes retire — the
            double-buffered stream, now pod-wide.
  chaining  multi-dispatch ops keep intermediates ON DEVICE with
            matched in/out shardings: verify re-encodes data shards
            and compares against the stored parity in a second
            dispatch whose inputs carry the first's out_shardings
            (only tiny [B, 4] count/first-index arrays ever return to
            the host); rebuild-with-check feeds rebuilt slabs straight
            into a re-encode+compare dispatch the same way.
  hardening ``timeout_s`` bounds how long the scheduler waits for a
            bucket slot (capped further by the ambient PR 6 deadline
            budget); `pod_*` wrappers fall back to the per-device
            schedulers on MeshError (and to them outright when the
            mesh is unavailable or the batch is too small to shard).

The bucket-handoff state machine (reader pool -> pack -> upload ->
dispatch -> FIFO retire -> per-volume writer lanes) reuses
`ec/fleet.TaggedPipeline` and is backend-injectable so the PR 10
schedule explorer can drive it under seeded interleavings
(tests/test_mesh_fleet.py).

Everything is lazy: importing this module touches no jax state, and
nothing queries devices or spawns a thread until a pod entry point
actually runs with the mesh enabled
(test_perf_gates.test_mesh_disabled_overhead).
"""

from __future__ import annotations

import functools
import os
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from seaweedfs_tpu.ec import fleet as _fleet
from seaweedfs_tpu.ec import encoder as _encoder
from seaweedfs_tpu.ec.encoder import (
    LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, shard_file_name)
from seaweedfs_tpu.ops.rs_code import ReedSolomon, DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.resilience import deadline as deadline_mod
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import (
    FleetMeshBucketsCounter, FleetMeshFallbacksCounter)
from seaweedfs_tpu.util import wlog

log = wlog.logger("mesh")

# Bytes of .dat data per fused bucket (before lane padding): the
# [dp, 10, span] upload unit. 32MB keeps two in-flight buckets well
# under host memory while large enough that dispatch latency amortizes.
DEFAULT_BUCKET_MB = 32

# Default bound on waiting for a bucket slot (i.e. on the slowest
# in-flight dispatch): a wedged chip/rendezvous surfaces as MeshError
# and the pod wrappers fall back instead of hanging the caller.
DEFAULT_TIMEOUT_S = 30.0

# Encode passes hold 14 output fds per volume; 64 volumes per mesh
# pass (896 fds) stays under the default 1024 RLIMIT_NOFILE soft
# limit. pod_write_ec_files chunks bigger batches into back-to-back
# passes rather than letting EMFILE demote them to the fleet path.
MAX_VOLUMES_PER_PASS = 64

PARITY_SHARDS = TOTAL_SHARDS - DATA_SHARDS


class MeshError(RuntimeError):
    """Base: the unified mesh scheduler could not complete the pass."""


class MeshUnavailable(MeshError):
    """No usable multi-device mesh (single device, jax unavailable)."""


class MeshDispatchTimeout(MeshError):
    """A bucket dispatch exceeded timeout_s / the ambient deadline."""


class MeshVerifyMismatch(MeshError):
    """rebuild(verify=True): re-encoded stripes disagree with parity."""


class MeshStats:
    """Per-pass introspection (bench --mesh occupancy/overlap source)."""

    __slots__ = ("op", "buckets", "spans", "slots", "bytes_in",
                 "wall_s")

    def __init__(self, op: str):
        self.op = op
        self.buckets = 0
        self.spans = 0        # live (non-padding) spans packed
        self.slots = 0        # buckets * dp
        self.bytes_in = 0     # live .dat/.ecNN bytes uploaded
        self.wall_s = 0.0

    @property
    def occupancy(self) -> float:
        """Live spans per bucket slot: 1.0 = every dp slot earned."""
        return self.spans / self.slots if self.slots else 0.0


def _geometry(mesh) -> Tuple[int, int]:
    """(dp, sp) from a Mesh — or a plain (dp, sp) tuple, the seam the
    schedule-explorer tests use to drive the handoff without jax."""
    if isinstance(mesh, tuple):
        return mesh
    return mesh.shape["dp"], mesh.shape["sp"]


def _lanes_for(span_bytes: int, sp: int) -> int:
    return -(-span_bytes // sp) * sp


@functools.lru_cache(maxsize=1)
def _default_mesh():
    """The process-wide mesh over all devices (built on FIRST use: the
    disabled path must never query jax devices)."""
    import jax

    devices = jax.devices()
    if len(devices) < 2:
        raise MeshUnavailable(
            f"{len(devices)} jax device(s): nothing to shard over")
    from seaweedfs_tpu.parallel.mesh import make_mesh
    return make_mesh(devices=devices)


def _resolve_mesh(mesh):
    if mesh is None:
        try:
            return _default_mesh()
        except MeshUnavailable:
            raise
        except Exception as e:
            raise MeshUnavailable(f"jax mesh unavailable: {e!r}") from e
    return mesh


# -- sharded device programs --------------------------------------------------
#
# One generic GF dispatch (encode AND rebuild are gf_linear with
# different matrices; jax.jit re-specializes per matrix/bucket shape,
# and every full bucket of an op shares one compile) plus the chained
# compare/recheck programs whose in_shardings MATCH the producer's
# out_shardings so intermediates never leave the devices.

@functools.lru_cache(maxsize=8)
def _shardings(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return (NamedSharding(mesh, P("dp", None, "sp")),
            NamedSharding(mesh, P()))


def _donate(mesh, *argnums) -> Tuple[int, ...]:
    # buffer donation is a no-op (with a per-call warning) on host
    # platforms; only donate where XLA actually reuses the buffer
    dev = next(iter(mesh.devices.flat))
    return tuple(argnums) if dev.platform not in ("cpu",) else ()


def _gf_local2d(m2, block):
    """One device's [b, S, n] block of a sharded bucket, encoded as a
    2D [S, b*n] GEMM: the map is per byte-column, so the flatten is
    free, and the 2D shape keeps XLA in its well-tiled f32 matmul path
    (the apply_matrix lesson — batched 3D int8 einsums compile poorly,
    ~1.5x slower end to end on the 8-device rig)."""
    import jax.numpy as jnp

    from seaweedfs_tpu.ops.rs_kernel import gf_linear_gemm

    b, s, n = block.shape
    flat = jnp.moveaxis(block, 1, 0).reshape(s, b * n)
    out = gf_linear_gemm(m2, flat)
    return jnp.moveaxis(out.reshape(out.shape[0], b, n), 0, 1)


def _shard_mapped(mesh, fn, in_specs, out_specs):
    """shard_map fn over the mesh, P('dp', None, 'sp') for bucket
    arrays ('data'), P() for replicated matrices ('rep')."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    lut = {"data": P("dp", None, "sp"), "rep": P(), "dp": P("dp")}
    pick = lambda s: lut[s]  # noqa: E731 - tiny spec table
    return shard_map(fn, mesh=mesh,
                     in_specs=tuple(pick(s) for s in in_specs),
                     out_specs=(tuple(pick(s) for s in out_specs)
                                if isinstance(out_specs, tuple)
                                else pick(out_specs)))


@functools.lru_cache(maxsize=8)
def _mesh_gf_fn(mesh):
    """jit'd GF map over the mesh: [B, S, N] uint8 -> [B, O, N], each
    device computing its own [B/dp, S, N/sp] block as a local 2D GEMM
    (no collectives — the matrix is replicated, the map per-column)."""
    import jax

    return jax.jit(
        _shard_mapped(mesh, _gf_local2d, ("rep", "data"), "data"),
        donate_argnums=_donate(mesh, 1))


@functools.lru_cache(maxsize=8)
def _mesh_compare_fn(mesh):
    """Chained verify dispatch: computed parity (still device-resident,
    in_shardings == the encode dispatch's out_shardings) vs the stored
    parity, masked to each span's valid compare length. Returns
    replicated [B, P] mismatch counts and first-mismatch lane indices —
    the only bytes that cross back to the host."""
    import jax
    import jax.numpy as jnp

    data_spec, rep = _shardings(mesh)

    @functools.partial(
        jax.jit, in_shardings=(data_spec, data_spec, rep),
        out_shardings=(rep, rep), donate_argnums=_donate(mesh, 0, 1))
    def compare(parity, stored, limits):
        pos = jax.lax.broadcasted_iota(jnp.int32, parity.shape, 2)
        mask = (parity != stored) & (pos < limits[:, :, None])
        counts = jnp.sum(mask, axis=-1, dtype=jnp.int32)
        firsts = jnp.argmax(mask, axis=-1).astype(jnp.int32)
        return counts, firsts

    return compare


@functools.lru_cache(maxsize=32)
def _mesh_rebuild_fn(mesh, present: Tuple[int, ...],
                     missing: Tuple[int, ...], check: bool):
    """Rebuild dispatch for one (present, missing) signature: the first
    DATA_SHARDS present rows of the [B, n_present, N] source feed the
    decode map. With check=True the rebuilt slab is CHAINED — still on
    device, matched shardings — into a re-encode of the full stripe's
    data rows, compared against its parity rows: [B] mismatch counts
    (psum'd over the lane shards, the op's only collective)."""
    import jax
    import jax.numpy as jnp

    def rebuild(dec_m2, enc_m2, src):
        rebuilt = _gf_local2d(dec_m2, src[:, :DATA_SHARDS, :])
        if not check:
            return rebuilt
        # assemble the full 14-row stripe from survivors + rebuilt
        # (static indices: the signature is baked into the jit key)
        rows = []
        for sid in range(TOTAL_SHARDS):
            if sid in present:
                rows.append(src[:, present.index(sid), :])
            else:
                rows.append(rebuilt[:, missing.index(sid), :])
        full = jnp.stack(rows, axis=1)
        want = _gf_local2d(enc_m2, full[:, :DATA_SHARDS, :])
        bad = jnp.sum(
            (want != full[:, DATA_SHARDS:, :]).astype(jnp.int32),
            axis=(1, 2))
        return rebuilt, jax.lax.psum(bad, "sp")

    if check:
        return jax.jit(_shard_mapped(mesh, rebuild,
                                     ("rep", "rep", "data"),
                                     ("data", "dp")))
    return jax.jit(_shard_mapped(mesh, rebuild,
                                 ("rep", "rep", "data"), "data"))


@functools.lru_cache(maxsize=64)
def _decode_m2_cached(present: Tuple[int, ...], missing: Tuple[int, ...]):
    from seaweedfs_tpu.ops.rs_kernel import m2_bits

    rs = ReedSolomon()
    return m2_bits(rs._decode_matrix(present[:DATA_SHARDS], missing))


def _decode_m2(present: Sequence[int], missing: Sequence[int]):
    # cached per (present, missing) signature: the GF(2^8) inversion
    # sits on the degraded-read hot path and repeats across batches
    return _decode_m2_cached(tuple(present), tuple(missing))


def sharded_reconstruct(mesh, present: Sequence[int],
                        missing: Sequence[int],
                        src: np.ndarray) -> np.ndarray:
    """One fused [B, 10, span] reconstruct over the mesh — the
    degraded-read decode fleet's dispatch seam (reads/decode_fleet.py
    routes here when the server runs with -ec.mesh). Pads B up to a dp
    multiple and span up to an sp multiple; trims on return."""
    import jax

    mesh = _resolve_mesh(mesh)
    dp, sp = _geometry(mesh)
    data_spec, _ = _shardings(mesh)
    b, rows, span = src.shape
    bp = -(-b // dp) * dp
    # quantize the lane width to a power-of-two grid: encode/verify fix
    # one bucket shape per pass, but degraded-read spans track request
    # lengths — without the grid every new span compiles a fresh
    # shard_map program on the latency-sensitive read path
    lanes = _lanes_for(1 << max(0, (span - 1).bit_length()), sp)
    if (bp, lanes) != (b, span):
        padded = np.zeros((bp, rows, lanes), dtype=np.uint8)
        padded[:b, :, :span] = src
        src = padded
    x = jax.device_put(src, data_spec)
    from seaweedfs_tpu.ops.rs_kernel import parity_m2_bits

    out = _mesh_rebuild_fn(mesh, tuple(present), tuple(missing), False)(
        _decode_m2(present, missing), parity_m2_bits(), x)
    return np.asarray(out)[:b, :, :span]


# -- per-pass machinery -------------------------------------------------------

class _ShardFiles:
    """Per-volume shard fds held open for the whole pass (the
    satellite finding: per-span open/"ab"/close cost thousands of
    syscalls per volume). All of one volume's writes run FIFO on one
    writer lane, so each fd has a single writing thread; the outer map
    is fully built before any lane starts."""

    def __init__(self, bases: Sequence[str]):
        self._fds: Dict[str, Dict[int, object]] = {b: {} for b in bases}

    def create(self, base: str, sids: Sequence[int]) -> None:
        """Truncate + hold open each of `base`'s output shards."""
        for sid in sids:
            self._fds[base][sid] = open(shard_file_name(base, sid), "wb")

    def write(self, base: str, sid: int, parts: Sequence) -> None:
        f = self._fds[base][sid]
        for p in parts:
            f.write(p)

    def close(self) -> None:
        for fds in self._fds.values():
            for f in fds.values():
                f.close()
            fds.clear()


class _SliceHandle:
    """Adapt one bucket's dispatch output (an async device array, a
    tuple of them, or plain ndarrays from an injected test dispatch) to
    TaggedPipeline's list-of-per-span-outputs contract: result()
    fetches the bucket output once — for jax arrays np.asarray IS the
    device wait — and hands each live slot its slice."""

    def __init__(self, raw, n_live: int):
        self._raw = raw
        self._n = n_live

    def result(self) -> List:
        if isinstance(self._raw, tuple):  # chained: (counts, firsts)
            parts = [np.asarray(o) for o in self._raw]
            return [tuple(p[i] for p in parts)
                    for i in range(self._n)]
        out = np.asarray(self._raw)
        return [out[i] for i in range(self._n)]


class _JaxDispatch:
    """Real device dispatch: upload the packed bucket with the batch
    sharding (the double-buffer transfer half) and issue the op's
    program(s). Returned handles resolve asynchronously — the retire
    thread's fetch IS the device wait."""

    def __init__(self, mesh, op: str):
        import jax

        from seaweedfs_tpu.ops.rs_kernel import (note_placement,
                                                 parity_m2_bits)

        self._jax = jax
        self._note_placement = note_placement
        self._mesh = mesh
        self._op = op
        self._data_spec, _ = _shardings(mesh)
        self._enc_m2 = parity_m2_bits()
        self._gf = _mesh_gf_fn(mesh)
        self._compare = _mesh_compare_fn(mesh) if op == "verify" else None

    def __call__(self, bucket: np.ndarray, aux=None):
        with _fleet._StageTimer("upload", bytes=bucket.nbytes):
            x = self._jax.device_put(bucket, self._data_spec)
            self._note_placement(x)
            if self._op == "verify":
                stored = self._jax.device_put(aux[0], self._data_spec)
        if self._op == "verify":
            parity = self._gf(self._enc_m2, x)
            return self._compare(parity, stored, aux[1])
        if self._op == "encode":
            return self._gf(self._enc_m2, x)
        # rebuild: aux = (dec_m2, present, missing, check)
        dec_m2, present, missing, check = aux
        return _mesh_rebuild_fn(self._mesh, present, missing, check)(
            dec_m2, self._enc_m2, x)


class _InlineResult:
    __slots__ = ("_v",)

    def __init__(self, v):
        self._v = v

    def result(self):
        return self._v


class _InlinePool:
    """readers=0: reads run inline on the dispatch loop (no futures).
    The schedule-explorer tests use this so the explored machine is
    exactly the bucket handoff — Future.result() rides Condition.wait,
    which the cooperative scheduler refuses by design."""

    def submit(self, fn, *args, **kw):
        return _InlineResult(fn(*args, **kw))

    def shutdown(self, wait: bool = True) -> None:
        return None


class _MeshRun:
    """One unified-scheduler pass: ONE reader pool, ONE dispatch loop,
    depth-bounded in-flight buckets retiring FIFO through a
    TaggedPipeline onto per-volume writer lanes.

    The dispatch loop runs on the CALLER thread; `submit` blocks only
    when `depth` buckets are already in flight, and that wait is
    bounded by timeout_s and the ambient deadline budget — the
    rendezvous/dispatch hardening that lets pod wrappers fall back
    instead of hanging on a wedged chip.
    """

    def __init__(self, dispatch: Callable, op: str, readers: int,
                 depth: int, timeout_s: float):
        self._dispatch = dispatch
        self._stats = MeshStats(op)
        self._timeout_s = timeout_s
        if readers <= 0:
            self._pool = _InlinePool()
        else:
            # lint: thread-ok(per-pass reader pool; work items are explicit, no ambient request state)
            self._pool = ThreadPoolExecutor(
                max_workers=readers, thread_name_prefix="mesh-read")
        self._pipe = _fleet.TaggedPipeline(depth=max(1, depth))
        self._abandoned = False
        # labels() locks per call; the op is fixed for the pass
        self._buckets_counter = FleetMeshBucketsCounter.labels(op)

    @property
    def stats(self) -> MeshStats:
        return self._stats

    @property
    def pool(self) -> ThreadPoolExecutor:
        return self._pool

    def _slot_timeout(self) -> Optional[float]:
        t = self._timeout_s if self._timeout_s > 0 else None
        rem = deadline_mod.remaining()
        if rem is not None:
            if rem <= 0:
                # budget spent mid-pass: finish() must not wait on a
                # drain that can sit behind a wedged dispatch — mark
                # the pass abandoned, same as the queue.Full arms
                self._abandoned = True
                raise deadline_mod.DeadlineExceeded("mesh dispatch")
            t = rem if t is None else min(t, rem)
        return t

    def submit(self, bucket: np.ndarray, aux,
               tagged: Sequence[Tuple[int, Callable]],
               live_bytes: int) -> None:
        st = self._stats
        timeout_s = self._slot_timeout()  # may raise DeadlineExceeded
        with _fleet._StageTimer("dispatch", batch=len(tagged)):
            handle = _SliceHandle(self._dispatch(bucket, aux),
                                  len(tagged))
        st.buckets += 1
        st.spans += len(tagged)
        st.slots += bucket.shape[0]
        st.bytes_in += live_bytes
        self._buckets_counter.inc()
        try:
            self._pipe.submit(handle, tagged, timeout_s=timeout_s)
        except queue.Full:
            self._abandoned = True
            raise MeshDispatchTimeout(
                f"mesh {st.op}: no bucket retired within "
                f"{self._timeout_s}s ({st.buckets} dispatched)")

    def write(self, tag: int, fn: Callable[[], None]) -> None:
        """Data-shard write on `tag`'s lane, stall-bounded like
        submit(): a writer lane wedged past the slot timeout abandons
        the pass instead of blocking the dispatch loop forever."""
        try:
            self._pipe.write(tag, fn, timeout_s=self._slot_timeout())
        except queue.Full:
            self._abandoned = True
            raise MeshDispatchTimeout(
                f"mesh {self._stats.op}: writer lane {tag} stayed full "
                f"for {self._timeout_s}s")

    def finish(self, error: bool) -> None:
        """Tear down pools; drain the pipeline unless the pass timed
        out (a wedged retire thread cannot be joined — it is daemon and
        gets abandoned, the documented fallback contract)."""
        self._pool.shutdown(wait=not self._abandoned)
        if not self._abandoned:
            if error:
                try:
                    self._pipe.drain()
                # lint: swallow-ok(first error already propagating; drain is cleanup)
                except Exception:
                    pass
            else:
                self._pipe.drain()


def _drive_buckets(gen, dp: int, readers: int,
                   submit_read: Callable, flush: Callable) -> None:
    """THE fill/pack/flush dispatch-driver loop (ROADMAP item 2(e)):
    pull work units off `gen`, keep up to max(readers, 2*dp) reads in
    flight on the run's pool, retire them in submission order into
    dp-sized packs, and hand each full (or final short) pack to
    `flush`, which builds the fused bucket and submits its dispatch.

    Encode, verify, and rebuild used to carry a private copy of this
    loop each; they now all drive their passes through this ONE
    function — `submit_read(item) -> future-like` and
    `flush([(item, result), ...])` carry the per-op shape — so the
    schedule-explorer interleavings that prove the encode seam
    (tests/test_mesh_fleet.py) provably cover all three ops.
    """
    inflight: deque = deque()
    prefetch = max(readers, 2 * dp)

    def fill() -> None:
        while len(inflight) < prefetch:
            nxt = next(gen, None)
            if nxt is None:
                break
            inflight.append((nxt, submit_read(nxt)))

    fill()
    pack: List = []
    while inflight:
        item, fut = inflight.popleft()
        pack.append((item, fut.result()))
        fill()
        if len(pack) == dp or not inflight:
            flush(pack)
            pack = []


def _span_geometry(dp: int, sp: int, small_block: int,
                   bucket_mb: int) -> Tuple[int, int]:
    """(span_rows, lanes): rows of small_block per span slot, and the
    sp-padded lane width every bucket of the pass shares."""
    bucket_bytes = max(1, bucket_mb) << 20
    span_rows = max(1, bucket_bytes // (dp * DATA_SHARDS * small_block))
    return span_rows, _lanes_for(span_rows * small_block, sp)


class _FdCache:
    """Per-pass read-side fd cache (ROADMAP item 2(d)): verify and
    rebuild used to reopen each shard file once PER SPAN — a 1GB
    shard at 32MB buckets cost ~32 open/close pairs per shard file,
    and the whole pass paid them again on every shard row. One raw
    O_RDONLY fd per path instead, shared by the concurrent reader
    pool: reads go through positionless ``os.preadv`` straight into
    the destination rows, so no seek races and no intermediate bytes
    objects. Passes are chunked to MAX_VOLUMES_PER_PASS volumes (the
    same RLIMIT_NOFILE budget that caps encode), so the cache tops
    out at 14 fds per volume x 64 volumes under the default 1024
    soft limit."""

    __slots__ = ("_fds", "_lock")

    def __init__(self):
        self._lock = threading.Lock()
        self._fds: Dict[str, int] = {}  # guarded_by(self._lock)

    def fd(self, path: str) -> int:
        with self._lock:
            fd = self._fds.get(path)
            if fd is None:
                fd = os.open(path, os.O_RDONLY)
                self._fds[path] = fd
            return fd

    def pread_into(self, path: str, offset: int, view) -> int:
        """Fill `view` (a writable memoryview) from path@offset;
        returns bytes read (short at EOF, like readinto)."""
        return os.preadv(self.fd(path), [view], offset)

    def close(self) -> None:
        with self._lock:
            fds = list(self._fds.values())
            self._fds.clear()
        for fd in fds:
            try:
                os.close(fd)
            except OSError:
                pass


def _read_shard_rows(base: str, sids: Sequence[int], shard_size: int,
                     offset: int, lanes: int,
                     parent: Optional[int],
                     fds: Optional[_FdCache] = None) -> np.ndarray:
    """[len(sids), lanes] slice at `offset` of the named shard files,
    zero-padded past `shard_size`, in an array of its own (any row
    set: the rebuild check reads ALL present rows, not just the
    decode's ten). With an
    _FdCache the rows fill via os.preadv on cached fds; without one
    (host-fleet callers) each file opens per call as before."""
    with _fleet._StageTimer("read", parent=parent,
                            vol=os.path.basename(base)):
        src = np.zeros((len(sids), lanes), dtype=np.uint8)
        want = min(lanes, max(shard_size - offset, 0))
        if want > 0:
            for row, sid in enumerate(sids):
                if fds is not None:
                    fds.pread_into(shard_file_name(base, sid), offset,
                                   memoryview(src[row])[:want])
                else:
                    with open(shard_file_name(base, sid), "rb") as f:
                        f.seek(offset)
                        f.readinto(memoryview(src[row])[:want])
        return src


def _read_span_matrix(base: str, row0: int, rows: int, row_bytes: int,
                      small_block: int,
                      parent: Optional[int]) -> np.ndarray:
    """Rows [row0, row0+rows) of one .dat as the shard-major
    [DATA_SHARDS, rows*small_block] matrix (row i is the span's bytes
    of .ec0i) — zero-padded past EOF."""
    with _fleet._StageTimer("read", parent=parent,
                            vol=os.path.basename(base)):
        with open(base + ".dat", "rb") as f:
            buf = _encoder._read_padded(f, row0 * row_bytes,
                                        rows * row_bytes)
        return np.ascontiguousarray(np.moveaxis(
            buf.reshape(rows, DATA_SHARDS, small_block),
            0, 1)).reshape(DATA_SHARDS, rows * small_block)


# -- encode -------------------------------------------------------------------

def mesh_write_ec_files(base_names: Sequence[str], mesh=None,
                        small_block: int = SMALL_BLOCK_SIZE,
                        bucket_mb: int = DEFAULT_BUCKET_MB,
                        readers: int = _fleet.FLEET_READERS,
                        depth: int = _fleet.FLEET_DEPTH,
                        timeout_s: float = DEFAULT_TIMEOUT_S,
                        _dispatch: Optional[Callable] = None
                        ) -> MeshStats:
    """Encode MANY volumes' .ec00-.ec13 through the unified mesh
    scheduler: one reader pool feeds fixed-shape [dp, 10, lanes]
    buckets (spans from any volumes, round-robin so per-volume row
    order is preserved by construction), each uploaded with the batch
    sharding while the previous bucket computes. Byte-identical to
    `write_ec_files` per volume (uniform small rows; oversized volumes
    are the caller's job — see pod_write_ec_files)."""
    import time

    if not base_names:
        return MeshStats("encode")
    dat_sizes = {}
    for b in base_names:
        dat_sizes[b] = os.path.getsize(b + ".dat")
        if dat_sizes[b] > DATA_SHARDS * LARGE_BLOCK_SIZE:
            raise ValueError(
                f"{b}.dat needs large-row striping — route through "
                "pod_write_ec_files/write_ec_files")
    if _dispatch is None:
        mesh = _resolve_mesh(mesh)
    dp, sp = _geometry(mesh)
    span_rows, lanes = _span_geometry(dp, sp, small_block, bucket_mb)
    row_bytes = DATA_SHARDS * small_block
    vols = []
    for tag, b in enumerate(base_names):
        vols.append(_fleet._VolState(
            b, dat_sizes[b], -(-dat_sizes[b] // row_bytes), tag))
    dispatch = _dispatch if _dispatch is not None \
        else _JaxDispatch(mesh, "encode")
    run = _MeshRun(dispatch, "encode", readers, depth, timeout_s)
    files = _ShardFiles(base_names)
    t0 = time.perf_counter()
    root = trace.span("fleet.mesh.encode", volumes=len(vols),
                      dp=dp, sp=sp)
    root.__enter__()
    token = root.token()
    ok = False
    try:
        with _fleet._StageTimer("write", setup=len(vols)):
            for v in vols:
                files.create(v.base, range(TOTAL_SHARDS))
        gen = _fleet._round_robin_spans(
            [v for v in vols if v.n_rows > 0], span_rows)

        def submit_read(item):
            v, row0, rows = item
            return run.pool.submit(
                _read_span_matrix, v.base, row0, rows, row_bytes,
                small_block, token)

        def flush(pack) -> None:
            bucket = np.zeros((dp, DATA_SHARDS, lanes), dtype=np.uint8)
            tagged, live = [], 0
            for slot, ((v, _row0, rows), m) in enumerate(pack):
                w = rows * small_block
                bucket[slot, :, :w] = m
                live += w * DATA_SHARDS
                # data shards are straight copies: onto the volume's
                # lane NOW (pack order == per-volume row order)
                run.write(v.tag, functools.partial(
                    _write_data_rows, files, v.base, m))
                tagged.append((v.tag, functools.partial(
                    _write_parity_rows, files, v.base, w)))
            run.submit(bucket, None, tagged, live)

        _drive_buckets(gen, dp, readers, submit_read, flush)
        ok = True
    finally:
        try:
            run.finish(error=not ok)
        finally:
            files.close()
            run.stats.wall_s = time.perf_counter() - t0
            root.__exit__(None, None, None)
    return run.stats


def _write_data_rows(files: _ShardFiles, base: str,
                     m: np.ndarray) -> None:
    for i in range(DATA_SHARDS):
        files.write(base, i, [m[i]])


def _write_parity_rows(files: _ShardFiles, base: str, w: int,
                       out: np.ndarray) -> None:
    """One retired slot's parity [P, lanes]: append the live prefix."""
    for p in range(out.shape[0]):
        files.write(base, DATA_SHARDS + p,
                    [np.ascontiguousarray(out[p, :w])])


# -- verify -------------------------------------------------------------------

def mesh_verify_ec_files(base_names: Sequence[str], mesh=None,
                         bucket_mb: int = DEFAULT_BUCKET_MB,
                         readers: int = _fleet.FLEET_READERS,
                         depth: int = _fleet.FLEET_DEPTH,
                         timeout_s: float = DEFAULT_TIMEOUT_S,
                         throttler=None,
                         _dispatch: Optional[Callable] = None
                         ) -> Dict[str, "_fleet.VerifyResult"]:
    """`fleet_verify_ec_files` on the unified mesh scheduler: data
    shards are re-encoded in sharded buckets and compared against the
    stored parity IN A CHAINED DISPATCH — the recomputed parity never
    leaves the devices; only [B, P] mismatch counts and first-offset
    indices come home. Result semantics match the fleet verifier
    byte-for-byte (truncated parity tails count every absent byte)."""
    import time

    results: Dict[str, _fleet.VerifyResult] = {}
    live: List[Tuple[str, int, List[int], Dict[int, int]]] = []
    for base in base_names:
        r = _fleet.VerifyResult()
        results[base] = r
        present = [i for i in range(TOTAL_SHARDS)
                   if os.path.exists(shard_file_name(base, i))]
        r.missing = [i for i in range(TOTAL_SHARDS) if i not in present]
        data_present = [i for i in present if i < DATA_SHARDS]
        parity_present = [i for i in present if i >= DATA_SHARDS]
        if len(data_present) < DATA_SHARDS or not parity_present:
            r.verified = False
            continue
        r.parity_checked = parity_present
        sizes = {sid: os.path.getsize(shard_file_name(base, sid))
                 for sid in parity_present}
        live.append((base, os.path.getsize(shard_file_name(base, 0)),
                     parity_present, sizes))
    if not live:
        return results
    if _dispatch is None:
        mesh = _resolve_mesh(mesh)
    dp, sp = _geometry(mesh)
    # per-slot span: a dp-slot slice of one bucket, capped at the
    # largest shard (small fleets must not encode padding slabs)
    bucket_bytes = max(1, bucket_mb) << 20
    span = max(1, min(bucket_bytes // (dp * DATA_SHARDS),
                      max(size for _, size, _, _ in live)))
    lanes = _lanes_for(span, sp)
    vols = [( _fleet._VolState(base, size, -(-size // span) if size else 0,
                               tag), parity, sizes)
            for tag, (base, size, parity, sizes) in enumerate(live)]
    meta = {v.tag: (parity, sizes, v) for v, parity, sizes in vols}
    dispatch = _dispatch if _dispatch is not None \
        else _JaxDispatch(mesh, "verify")
    run = _MeshRun(dispatch, "verify", readers, depth, timeout_s)
    root = trace.span("fleet.mesh.verify", volumes=len(vols),
                      dp=dp, sp=sp)
    root.__enter__()
    token = root.token()
    t0 = time.perf_counter()

    def gen_spans():
        for v, row0, _rows in _fleet._round_robin_spans(
                [v for v, _, _ in vols], 1):
            yield v, row0 * span

    fds = _FdCache()   # read-side fds cached for the whole pass

    def read_one(v: "_fleet._VolState", offset: int):
        parity, sizes, _ = meta[v.tag]
        data = _read_shard_rows(v.base, range(DATA_SHARDS), v.dat_size,
                                offset, lanes, token, fds=fds)
        stored = np.zeros((PARITY_SHARDS, lanes), dtype=np.uint8)
        valid = min(span, v.dat_size - offset)
        limits = np.zeros(PARITY_SHARDS, dtype=np.int32)
        for sid in parity:
            have = min(max(sizes[sid] - offset, 0), valid)
            limits[sid - DATA_SHARDS] = have
            if have > 0:
                fds.pread_into(
                    shard_file_name(v.base, sid), offset,
                    memoryview(stored[sid - DATA_SHARDS])[:have])
        return data, stored, limits

    ok = False
    try:
        gen = gen_spans()

        def submit_read(item):
            v, offset = item
            if throttler is not None:
                parity, _, _ = meta[v.tag]
                throttler.maybe_slowdown(
                    (DATA_SHARDS + len(parity)) * span)
            return run.pool.submit(read_one, v, offset)

        def retire_span(v: "_fleet._VolState", offset: int, out) -> None:
            counts, firsts = out
            parity, sizes, _ = meta[v.tag]
            valid = min(span, v.dat_size - offset)
            with _fleet._StageTimer("verify",
                                    vol=os.path.basename(v.base)):
                r = results[v.base]
                for sid in parity:
                    k = sid - DATA_SHARDS
                    have = min(max(sizes[sid] - offset, 0), valid)
                    n = int(counts[k])
                    if n:
                        r.parity_mismatch[sid] = \
                            r.parity_mismatch.get(sid, 0) + n
                        r.first_mismatch.setdefault(
                            sid, offset + int(firsts[k]))
                    if have < valid:
                        # truncated parity: every absent byte the data
                        # shards vouch for is a mismatch (fleet rule)
                        r.parity_mismatch[sid] = \
                            r.parity_mismatch.get(sid, 0) + (valid - have)
                        r.first_mismatch.setdefault(sid, offset + have)
                r.bytes_verified += DATA_SHARDS * valid
                r.spans += 1

        def flush(pack) -> None:
            bucket = np.zeros((dp, DATA_SHARDS, lanes), dtype=np.uint8)
            stored = np.zeros((dp, PARITY_SHARDS, lanes), dtype=np.uint8)
            limits = np.zeros((dp, PARITY_SHARDS), dtype=np.int32)
            tagged, livebytes = [], 0
            for slot, ((v, offset), (d, s, lim)) in enumerate(pack):
                bucket[slot] = d
                stored[slot] = s
                limits[slot] = lim
                livebytes += DATA_SHARDS * min(span,
                                               max(v.dat_size - offset, 0))
                tagged.append((v.tag, functools.partial(
                    retire_span, v, offset)))
            run.submit(bucket, (stored, limits), tagged, livebytes)

        _drive_buckets(gen, dp, readers, submit_read, flush)
        ok = True
    finally:
        try:
            run.finish(error=not ok)
        finally:
            fds.close()
            run.stats.wall_s = time.perf_counter() - t0
            root.__exit__(None, None, None)
    return results


# -- rebuild ------------------------------------------------------------------

def mesh_rebuild_ec_files(base_names: Sequence[str], mesh=None,
                          wanted: Optional[List[int]] = None,
                          bucket_mb: int = DEFAULT_BUCKET_MB,
                          readers: int = _fleet.FLEET_READERS,
                          depth: int = _fleet.FLEET_DEPTH,
                          timeout_s: float = DEFAULT_TIMEOUT_S,
                          check: bool = False) -> Dict[str, List[int]]:
    """`fleet_rebuild_ec_files` on the unified mesh scheduler: volumes
    sharing a (present, missing) signature share decode-matrix
    dispatches, bucketed over the whole mesh. With check=True every
    rebuilt slab is chained (on device, matched shardings) into a
    re-encode of its full stripe against the surviving parity; any
    disagreement raises MeshVerifyMismatch — corrupt survivors cannot
    silently mint corrupt shards."""
    mesh = _resolve_mesh(mesh)
    wanted_set = None if wanted is None else set(wanted)
    rebuilt: Dict[str, List[int]] = {}
    groups: Dict[Tuple[Tuple[int, ...], ...],
                 List[Tuple[str, int]]] = {}
    for base in base_names:
        present = [i for i in range(TOTAL_SHARDS)
                   if os.path.exists(shard_file_name(base, i))]
        absent = [i for i in range(TOTAL_SHARDS) if i not in present]
        write = absent if wanted_set is None \
            else [i for i in absent if i in wanted_set]
        rebuilt[base] = write
        if not write:
            continue
        if len(present) < DATA_SHARDS:
            raise ValueError(
                f"cannot rebuild {base}: only {len(present)} shards "
                "present")
        # check mode re-encodes the FULL stripe against surviving
        # parity, so every absent shard must be decoded even when the
        # caller only wants a subset written; plain rebuild decodes
        # just the wanted ones
        missing = absent if check else write
        shard_size = os.path.getsize(shard_file_name(base, present[0]))
        groups.setdefault((tuple(present), tuple(missing),
                           tuple(write)),
                          []).append((base, shard_size))
    for (present, missing, write), members in groups.items():
        # same RLIMIT_NOFILE budget as encode/verify: the pass holds
        # one cached read fd per present shard (+ write fds), so big
        # signature groups run as back-to-back chunked passes
        for i in range(0, len(members), MAX_VOLUMES_PER_PASS):
            _mesh_rebuild_group(mesh, present, missing, write,
                                members[i:i + MAX_VOLUMES_PER_PASS],
                                bucket_mb, readers, depth, timeout_s,
                                check)
    return rebuilt


def _mesh_rebuild_group(mesh, present: Tuple[int, ...],
                        missing: Tuple[int, ...],
                        write: Tuple[int, ...],
                        members: List[Tuple[str, int]], bucket_mb: int,
                        readers: int, depth: int, timeout_s: float,
                        check: bool) -> None:
    import jax

    from seaweedfs_tpu.ops.rs_kernel import parity_m2_bits

    dp, sp = _geometry(mesh)
    # check mode reads ALL present rows (the recheck needs the stripe's
    # surviving parity); plain rebuild reads only the decode's 10
    n_rows = len(present) if check else DATA_SHARDS
    bucket_bytes = max(1, bucket_mb) << 20
    span = max(1, min(bucket_bytes // (dp * n_rows),
                      max(size for _, size in members)))
    lanes = _lanes_for(span, sp)
    vols = [_fleet._VolState(base, size, -(-size // span) if size else 0,
                             tag)
            for tag, (base, size) in enumerate(members)]
    dec = _decode_m2(present, missing)
    data_spec, _ = _shardings(mesh)
    fn = _mesh_rebuild_fn(mesh, present, missing, check)
    enc_m2 = parity_m2_bits()
    write_set = set(write)
    bad_vols: List[str] = []

    def dispatch(bucket, aux=None):
        with _fleet._StageTimer("upload", bytes=bucket.nbytes):
            x = jax.device_put(bucket, data_spec)
        return fn(dec, enc_m2, x)

    run = _MeshRun(dispatch, "rebuild", readers, depth, timeout_s)
    files = _ShardFiles([base for base, _ in members])
    root = trace.span("fleet.mesh.rebuild", volumes=len(members),
                      dp=dp, sp=sp, check=check)
    root.__enter__()
    token = root.token()

    fds = _FdCache()   # read-side fds cached for the whole pass

    def read_rows(v: "_fleet._VolState", offset: int) -> np.ndarray:
        return _read_shard_rows(v.base, present[:n_rows], v.dat_size,
                                offset, lanes, token, fds=fds)

    def retire_span(v: "_fleet._VolState", offset: int, out) -> None:
        if check:
            rows, bad = out
            if int(bad):
                bad_vols.append(v.base)
        else:
            rows = out
        valid = min(span, v.dat_size - offset)
        for row, sid in enumerate(missing):
            if sid in write_set:
                files.write(v.base, sid,
                            [np.ascontiguousarray(rows[row, :valid])])

    ok = False
    try:
        for v in vols:
            files.create(v.base, write)
        gen = ((v, row0 * span) for v, row0, _r in
               _fleet._round_robin_spans(vols, 1))

        def submit_read(item):
            return run.pool.submit(read_rows, *item)

        def flush(pack) -> None:
            bucket = np.zeros((dp, n_rows, lanes), dtype=np.uint8)
            tagged, livebytes = [], 0
            for slot, ((v, offset), rows) in enumerate(pack):
                bucket[slot] = rows
                livebytes += n_rows * min(span,
                                          max(v.dat_size - offset, 0))
                tagged.append((v.tag, functools.partial(
                    retire_span, v, offset)))
            run.submit(bucket, None, tagged, livebytes)

        _drive_buckets(gen, dp, readers, submit_read, flush)
        ok = True
    finally:
        try:
            run.finish(error=not ok)
        finally:
            fds.close()
            files.close()
            root.__exit__(None, None, None)
    if bad_vols:
        # the rebuilt shards for these volumes are corrupt
        # reconstructions of previously ABSENT files — unlink them so
        # presence scans never see them as servable (the
        # minted-corrupt-shard outcome the check exists to prevent)
        bad = sorted(set(bad_vols))
        for base in bad:
            for sid in write:
                try:
                    os.unlink(shard_file_name(base, sid))
                except FileNotFoundError:
                    pass
        raise MeshVerifyMismatch(
            "rebuilt stripes disagree with surviving parity: " +
            ", ".join(bad))


# -- the pod entry points (fallback ladder) -----------------------------------
#
# mesh when it can, per-device fleet schedulers when it can't, the
# per-volume path for large-row volumes — every consumer (ec.encode
# batches, scrub verify, lifecycle's grouped encode passes) calls ONE
# of these and gets the strongest scheduler the process supports.

def _fallback(op: str, reason: str, exc: Optional[BaseException] = None
              ) -> None:
    FleetMeshFallbacksCounter.labels(reason).inc()
    if exc is not None:
        log.warning("mesh %s fell back (%s): %r — rerunning on the "
                    "per-device fleet schedulers", op, reason, exc)


def pod_write_ec_files(base_names: Sequence[str], backend: str = "auto",
                       mesh=None, min_volumes: int = 0,
                       bucket_mb: int = DEFAULT_BUCKET_MB,
                       timeout_s: float = DEFAULT_TIMEOUT_S,
                       small_block: int = SMALL_BLOCK_SIZE,
                       **fleet_kw) -> str:
    """Encode a fleet of volumes on the strongest available scheduler.

    Ladder: (1) oversized volumes take the per-volume large-row path
    (identical rule to fleet_write_ec_files); (2) the rest ride the
    unified mesh scheduler when a multi-device mesh exists and the
    batch is worth sharding (>= min_volumes, default dp); (3) any
    MeshError — no mesh, dispatch timeout, a failed sharded program —
    falls back to the per-device fleet schedulers, re-encoding the
    unfinished volumes from scratch (output files are truncated at
    pass start, so a partial mesh attempt leaves nothing stale;
    already-completed 64-volume chunks are NOT redone). Returns the
    path taken: "mesh" | "fleet"."""
    big = [b for b in base_names
           if os.path.getsize(b + ".dat") > DATA_SHARDS * LARGE_BLOCK_SIZE]
    for b in big:
        _encoder.write_ec_files(b, backend=backend,
                                small_block=small_block)
    big_set = set(big)
    rest = [b for b in base_names if b not in big_set]
    if not rest:
        return "fleet"
    done = 0
    try:
        m = _resolve_mesh(mesh)
        dp, _sp = _geometry(m)
        floor = min_volumes if min_volumes > 0 else dp
        if len(rest) < floor:
            raise MeshUnavailable(
                f"{len(rest)} volume(s) < min_volumes {floor}")
        # encode holds all 14 output fds per volume for the pass;
        # chunking keeps the fd footprint under the default 1024
        # RLIMIT_NOFILE soft limit even at the 256-volume pod scale
        # (otherwise EMFILE would demote exactly the big batches the
        # mesh exists for)
        for i in range(0, len(rest), MAX_VOLUMES_PER_PASS):
            mesh_write_ec_files(rest[i:i + MAX_VOLUMES_PER_PASS],
                                mesh=m, small_block=small_block,
                                bucket_mb=bucket_mb,
                                timeout_s=timeout_s)
            done = i + MAX_VOLUMES_PER_PASS
        return "mesh"
    except deadline_mod.DeadlineExceeded:
        raise   # the caller's budget is spent; a fallback can't help
    except MeshUnavailable as e:
        _fallback("encode", "unavailable")
        log.debug("mesh encode unavailable: %s", e)
    except MeshDispatchTimeout as e:
        _fallback("encode", "timeout", e)
    except Exception as e:  # noqa: BLE001 - any mesh failure demotes
        _fallback("encode", "error", e)
    from seaweedfs_tpu.parallel.mesh import fleet_write_ec_files_sharded

    fleet_write_ec_files_sharded(rest[done:], backend=backend,
                                 small_block=small_block, **fleet_kw)
    return "fleet"


def pod_verify_ec_files(base_names: Sequence[str], backend: str = "auto",
                        mesh=None, min_volumes: int = 0,
                        bucket_mb: int = DEFAULT_BUCKET_MB,
                        timeout_s: float = DEFAULT_TIMEOUT_S,
                        throttler=None,
                        **fleet_kw) -> Dict[str, "_fleet.VerifyResult"]:
    """Verify a fleet on the mesh when possible, with the same fallback
    ladder as pod_write_ec_files (verify writes nothing, so a failed
    mesh attempt simply re-verifies on the host fleet)."""
    try:
        m = _resolve_mesh(mesh)
        dp, _sp = _geometry(m)
        floor = min_volumes if min_volumes > 0 else dp
        if len(base_names) < floor:
            raise MeshUnavailable(
                f"{len(base_names)} volume(s) < min_volumes {floor}")
        # verify holds up to 14 cached read fds per volume (the
        # _FdCache); chunking keeps the pass under the same default
        # 1024 RLIMIT_NOFILE soft limit that caps encode
        out: Dict[str, _fleet.VerifyResult] = {}
        for i in range(0, len(base_names), MAX_VOLUMES_PER_PASS):
            out.update(mesh_verify_ec_files(
                base_names[i:i + MAX_VOLUMES_PER_PASS], mesh=m,
                bucket_mb=bucket_mb, timeout_s=timeout_s,
                throttler=throttler))
        return out
    except deadline_mod.DeadlineExceeded:
        raise
    except MeshUnavailable as e:
        _fallback("verify", "unavailable")
        log.debug("mesh verify unavailable: %s", e)
    except MeshDispatchTimeout as e:
        _fallback("verify", "timeout", e)
    except Exception as e:  # noqa: BLE001 - any mesh failure demotes
        _fallback("verify", "error", e)
    return _fleet.fleet_verify_ec_files(base_names, backend=backend,
                                        throttler=throttler, **fleet_kw)
