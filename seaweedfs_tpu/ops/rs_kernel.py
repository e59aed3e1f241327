"""JAX/XLA GF(2^8) linear-map kernel — the TPU compute core.

Formulation (TPU-first, not a port): a GF(2^8) Reed-Solomon encode
``parity[p, n] = XOR_d C[p,d] (x)gf data[d, n]`` is lifted to GF(2) bit
space.  Multiplication by a constant is GF(2)-linear, so with the byte
stream unpacked into 8 bit-planes the whole code becomes one integer
matmul:

    out_bits[(o,k), n] = sum_{d,j} M2[(o,k),(d,j)] * in_bits[(d,j), n]  mod 2

where ``M2 = gf256_matrix_to_gf2(C)`` (seaweedfs_tpu/ops/gf256.py).  The
contraction runs as an int8 matmul on the MXU (`preferred_element_type`
int32 — exact, sums <= 8*k < 2^31), and the mod-2 + bit-pack are cheap VPU
elementwise ops that XLA fuses around it.  No gathers, no data-dependent
control flow, static shapes throughout — exactly what XLA tiles well.

Equivalent reference behavior: the SIMD GF(2^8) mul in klauspost/reedsolomon
used by /root/reference weed/storage/erasure_coding/ec_encoder.go:179.

Shapes: shard data is [..., S, N] uint8 (leading dims = volume batch), the
coding matrix is [O, S] uint8. Batch dims ride jnp.einsum; sharding over a
device mesh is layered on in seaweedfs_tpu/parallel/.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

try:  # POSIX only, and RUSAGE_THREAD Linux only
    import resource
    _RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
except ImportError:
    _RUSAGE_THREAD = None

import jax
import jax.numpy as jnp
import numpy as np

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import (
    RsDispatchMinorFaultsCounter, RsDispatchSecondsHistogram,
    RsResultBuffersCounter, RsTailSlabsCounter)

_BIT_SHIFTS = tuple(range(8))

# The host side of one dispatch by phase. Children resolved once at
# import: labels() takes a lock per call.
_PHASE_HIST = {p: RsDispatchSecondsHistogram.labels(p)
               for p in ("stage", "place", "enqueue", "wait", "fetch",
                         "unstage")}
# Where a dispatch's result landed: memory the caller lent (touched
# before, so the copy pays no page faults) or a fresh array.
_RESULT_INTO = {state: RsResultBuffersCounter.labels(state)
                for state in ("lent", "fresh")}
# How a dispatch's short tail slab got its padding: `in_place` (the
# caller's array had the room: a slice) or `copied` (into a fresh
# zeroed array, whose pages every dispatch faults in anew).
_TAIL_PAD = {pad: RsTailSlabsCounter.labels(pad)
             for pad in ("in_place", "copied")}


# The phases that touch a slab's memory also count the calling
# thread's minor page faults (where the platform tells a thread's from
# the process's): fresh pages zeroed by the kernel inside a phase show
# here, faults on the runtime's own threads do not.
_PHASE_FAULTS = {p: RsDispatchMinorFaultsCounter.labels(p)
                 for p in ("place", "enqueue", "fetch", "unstage")} \
    if _RUSAGE_THREAD is not None else {}


class _FaultCountingTimer(trace.PhaseTimer):
    """A dispatch phase's timer that also adds the calling thread's
    minor page faults over the phase to the phase's counter; the two
    reads lie outside the timed interval."""

    __slots__ = ("_faults", "_minflt0")

    def __init__(self, phase: str, **tags):
        super().__init__(_PHASE_HIST[phase], "rs." + phase, **tags)
        self._faults = _PHASE_FAULTS[phase]

    def __enter__(self) -> "_FaultCountingTimer":
        self._minflt0 = resource.getrusage(_RUSAGE_THREAD).ru_minflt
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        suppress = super().__exit__(*exc)
        self._faults.inc(float(
            resource.getrusage(_RUSAGE_THREAD).ru_minflt - self._minflt0))
        return suppress


def _phase(phase: str, **tags) -> trace.PhaseTimer:
    """Timer of one dispatch phase; its span `rs.<phase>` nests under
    whatever span the calling thread has open (fleet.dispatch,
    fleet.retire, reads.decode, ...)."""
    if phase in _PHASE_FAULTS:
        return _FaultCountingTimer(phase, **tags)
    return trace.PhaseTimer(_PHASE_HIST[phase], "rs." + phase, **tags)

# Where dispatched input bytes were placed: (platform, device id) ->
# bytes, read off the arrays actually handed to the jitted programs
# (an evenly sharded array counts its per-device share on each device
# of its sharding). The evidence chip_smoke.py asserts on — that the
# device, and on a multi-chip host every device, did the work — and
# the per-device placement it prints.
_placed_lock = threading.Lock()
_placed_bytes: dict = {}


def note_placement(x: jax.Array) -> None:
    devices = x.sharding.device_set
    share = x.nbytes // len(devices)
    with _placed_lock:
        for d in devices:
            key = (d.platform, d.id)
            _placed_bytes[key] = _placed_bytes.get(key, 0) + share


def placed_bytes() -> dict:
    """Snapshot of {(platform, device id): input bytes dispatched}."""
    with _placed_lock:
        return dict(_placed_bytes)


def bits_expand(x: jnp.ndarray) -> jnp.ndarray:
    """[..., S, N] uint8 -> [..., S*8, N] int8 bit-planes (little-endian)."""
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape((8,) + (1,) * 1)
    # [..., S, 8, N]
    bits = (x[..., :, None, :] >> shifts) & jnp.uint8(1)
    s = x.shape[-2]
    return bits.reshape(x.shape[:-2] + (s * 8, x.shape[-1])).astype(jnp.int8)


def bits_pack(bits: jnp.ndarray) -> jnp.ndarray:
    """[..., O*8, N] {0,1} -> [..., O, N] uint8 (little-endian bit order)."""
    o8 = bits.shape[-2]
    o = o8 // 8
    b = bits.reshape(bits.shape[:-2] + (o, 8, bits.shape[-1])).astype(jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape((8, 1))
    # per-byte bits are disjoint powers of two: sum == bitwise-or, no overflow
    return jnp.sum(b << shifts, axis=-2, dtype=jnp.uint8)


def gf_linear(m2: jnp.ndarray, shards: jnp.ndarray) -> jnp.ndarray:
    """Apply a GF(2^8) linear map in bit space.

    m2:     [O*8, S*8] int8 GF(2) bit-matrix (from gf256_matrix_to_gf2)
    shards: [..., S, N] uint8
    returns [..., O, N] uint8
    """
    in_bits = bits_expand(shards)
    acc = jnp.einsum(
        "os,...sn->...on",
        m2,
        in_bits,
        preferred_element_type=jnp.int32,
    )
    out_bits = (acc & 1).astype(jnp.uint8)
    return bits_pack(out_bits)


def gf_linear_gemm(m2: jnp.ndarray, shards: jnp.ndarray) -> jnp.ndarray:
    """`gf_linear` with the GF(2) contraction run as a float32 GEMM.

    Exact, not approximate: every bit-plane dot product sums at most
    S*8 <= 112 ones (RS(10,4) maps), far inside float32's exact-integer
    range, so truncating the accumulator to int32 parity reproduces the
    int32 einsum bit for bit. The pod-scale mesh data plane
    (parallel/mesh_fleet.py) runs its per-device blocks through this
    entry; the fleet and serial dispatches keep the int path. Which of
    the two is faster: not measured on the chip (ROADMAP C2 waits for
    the four-chip cell).
    """
    in_bits = bits_expand(shards).astype(jnp.float32)
    acc = jnp.einsum("os,...sn->...on", m2.astype(jnp.float32), in_bits)
    out_bits = (acc.astype(jnp.int32) & jnp.int32(1)).astype(jnp.uint8)
    return bits_pack(out_bits)


@functools.partial(jax.jit, static_argnames=())
def _gf_linear_jit(m2, shards):
    return gf_linear(m2, shards)


# Lanes a block of the compare-and-count program covers: what it hands
# back is one count and one first lane a parity row and block, so a
# caller that lays its spans out on block boundaries can tell them apart.
# A power of two under the narrowest slab (_MIN_SLAB), so slabs hold
# whole blocks: 4 Mi lanes come back as 26 KB.
VERIFY_BLOCK = 1 << 12


@jax.jit
def _verify_jit(m2, stripe):
    """stripe [D + P, slab] uint8, m2 the parity rows' bit-matrix:
    re-encode rows 0..D-1, hold the result against rows D.. and return
    int32 [2, P, slab / VERIFY_BLOCK]: per parity row and block the
    count of differing bytes, and the first differing lane of the block
    (VERIFY_BLOCK where none differs)."""
    p = m2.shape[0] // 8
    differ = (gf_linear(m2, stripe[:-p]) != stripe[-p:]).reshape(
        p, -1, VERIFY_BLOCK)
    lane = jax.lax.broadcasted_iota(jnp.int32, differ.shape, 2)
    return jnp.stack([
        jnp.sum(differ, axis=-1, dtype=jnp.int32),
        jnp.min(jnp.where(differ, lane, VERIFY_BLOCK), axis=-1)])


@functools.lru_cache(maxsize=64)
def _m2_device(matrix_bytes: bytes, rows: int, cols: int) -> jnp.ndarray:
    m = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    return jnp.asarray(gf256.gf256_matrix_to_gf2(m).astype(np.int8))


def m2_bits(matrix: np.ndarray) -> jnp.ndarray:
    """GF(2^8) matrix [O, S] -> device GF(2) bit-matrix [O*8, S*8] int8.

    The shared entry for every caller that feeds gf_linear directly
    (parallel/mesh.py, bench.py, __graft_entry__.py) — one place owns the
    bit ordering and the int8-for-MXU dtype choice.
    """
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _m2_device(matrix.tobytes(), *matrix.shape)


def parity_m2_bits() -> jnp.ndarray:
    """Bit-matrix [32, 80] of the RS(10,4) parity rows."""
    from seaweedfs_tpu.ops.rs_code import coding_matrix, DATA_SHARDS
    return m2_bits(np.asarray(coding_matrix())[DATA_SHARDS:])


def apply_matrix(matrix: np.ndarray, shards) -> np.ndarray:
    """Host-friendly entry: GF(2^8) matrix [O, S] applied to [..., S, N] bytes.

    Expands the matrix to bits (cached per matrix) and runs the jitted
    kernel. Leading batch dims are flattened into the lane (N) dimension
    before dispatch — the map is per-byte-column, so [B, S, N] and
    [S, B*N] are the same computation, and the 2D shape keeps XLA in its
    well-tiled matmul path (batched 3D int8 einsums compile poorly).
    """
    return apply_matrix_async(matrix, shards).result()


class PendingApply:
    """An in-flight GF linear map: device dispatch already issued, result
    fetched (and slab padding stripped) on .result().

    JAX dispatch is asynchronous, so holding several of these overlaps
    device compute with host-side disk IO — the double-buffered encode
    stream SURVEY §7 calls for (vs the reference's serial 256KB loop,
    ec_encoder.go:120-136).
    """

    def __init__(self, parts, o: int, n: int, batch_shape, lanes: int,
                 out: Optional[np.ndarray] = None):
        self._parts = parts          # [(device_array, want, pos)]
        self._o = o
        self._n = n
        self._batch_shape = batch_shape
        self._lanes = lanes
        self._out = out              # the caller's [o, n], or None

    def result(self) -> np.ndarray:
        o, n = self._o, self._n
        out = self._out
        if n == 0:
            return out if out is not None else \
                np.zeros(self._batch_shape + (o, 0), dtype=np.uint8)
        if out is None:
            out = np.empty((o, n), dtype=np.uint8)
            _RESULT_INTO["fresh"].inc()
        else:
            _RESULT_INTO["lent"].inc()
        for res, want, pos in self._parts:
            # waiting apart from fetching: the device (a transfer's
            # tail and the kernel) against the device->host copy
            with _phase("wait"):
                res.block_until_ready()
            with _phase("fetch", bytes=res.nbytes):
                host = np.asarray(res)
            with _phase("unstage"):
                out[:, pos:pos + want] = host[:, :want]
        if self._batch_shape:
            with _phase("unstage"):
                out = np.moveaxis(
                    out.reshape(o, -1, self._lanes), 0, 1).reshape(
                    self._batch_shape + (o, self._lanes))
        return out


def apply_matrix_async(matrix: np.ndarray, shards, device=None,
                       out: Optional[np.ndarray] = None,
                       lanes: Optional[int] = None) -> PendingApply:
    """Dispatch apply_matrix without waiting for the device.

    Returns a PendingApply whose .result() blocks. Between submit and
    fetch the host is free to read the next slab from disk / write the
    previous one — the caller-visible half of the streaming pipeline.

    `device` pins the whole dispatch to ONE jax device instead of the
    default placement / lane sharding: the fleet scheduler
    (ec/fleet.py) runs one scheduler per device, so each scheduler's
    slabs must land on its own chip.

    `out` lends the result its memory, for a 2-D input [S, n] only: a
    [O, n] uint8 array whose rows are contiguous. .result() copies each
    fetched slab into it and returns it, where it would copy into a
    fresh np.empty((O, n)) — whose pages every dispatch faults in anew.
    A caller that keeps `out` between dispatches (ec/fleet._Staging)
    pays those faults once. What cannot take the result raises here,
    not in the thread that fetches it.

    `lanes` says how many lanes of a 2-D input [S, >= lanes] are the
    dispatch: the map runs over the first `lanes`, the result is
    [O, lanes], and the lanes after them are room the caller brings
    for a tail slab's padding (see `_submit_slabs`) — a staging buffer
    hands itself over up to `placed_lanes(lanes)` and the slab loop
    copies nothing.
    """
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    m2 = _m2_device(matrix.tobytes(), matrix.shape[0], matrix.shape[1])
    if device is not None:
        m2 = jax.device_put(m2, device)
    shards = np.asarray(shards, dtype=np.uint8)
    batch_shape = shards.shape[:-2]
    s, n = shards.shape[-2:]
    o = matrix.shape[0]
    if lanes is not None:
        if batch_shape or not 0 <= lanes <= n:
            raise ValueError(
                f"lanes={lanes} wants a 2-D input [S, >= lanes], not "
                f"{shards.shape}")
        n = lanes
    if out is not None:
        _check_lent(out, o, n, batch_shape)
    if n == 0:
        return PendingApply([], o, 0, batch_shape, n, out)
    if batch_shape:
        with _phase("stage"):
            flat = np.ascontiguousarray(
                np.moveaxis(shards.reshape((-1, s, n)), 1, 0)).reshape(s, -1)
        total = flat.shape[1]
    else:
        flat, total = shards, n
    parts = _submit_slabs(m2, flat, total, device=device)
    return PendingApply(parts, o, total, batch_shape, n, out)


class PendingVerify:
    """An in-flight compare-and-count over a stripe: .result() is
    (counts, firsts), each int32 [P, n / VERIFY_BLOCK] — per parity row
    and block of lanes the differing bytes and the first differing lane
    of the block. All that crosses back to the host."""

    def __init__(self, parts, p: int):
        self._parts = parts          # [(device_array, want, pos)]
        self._p = p

    def result(self):
        got = []
        for res, want, _pos in self._parts:
            with _phase("wait"):
                res.block_until_ready()
            with _phase("fetch", bytes=res.nbytes):
                host = np.asarray(res)
            got.append(host[:, :, :want // VERIFY_BLOCK])
        with _phase("unstage"):
            both = np.concatenate(got, axis=2) if got else \
                np.zeros((2, self._p, 0), dtype=np.int32)
        return both[0], both[1]


def verify_stripe_async(matrix: np.ndarray, stripe: np.ndarray,
                        device=None,
                        lanes: Optional[int] = None) -> PendingVerify:
    """Re-encode and compare on the device, without waiting for it.

    `matrix` [P, D] are the code's parity rows, `stripe` [D + P, n] a
    2-D uint8 array (a view will do; nothing is copied before the slab
    slices): rows 0..D-1 the data shards, rows D.. the STORED parity.
    The slab loop, the widths, a tail slab's padding (its blocks'
    counts are dropped) and the placement are `apply_matrix_async`'s,
    and so is `lanes`: the first `lanes` of the stripe are verified,
    what lies after them is the caller's room for the tail slab. What
    comes back a slab is its counts, KB where a map's result is rows.
    `lanes` (`n` without it) is a multiple of VERIFY_BLOCK: the caller
    lays out its spans on block boundaries."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    p, d = matrix.shape
    if not isinstance(stripe, np.ndarray) or stripe.dtype != np.uint8 \
            or stripe.ndim != 2 or stripe.shape[0] != d + p:
        raise ValueError(f"a stripe is a uint8 array [{d + p}, n], not "
                         f"{getattr(stripe, 'dtype', type(stripe).__name__)}"
                         f" {getattr(stripe, 'shape', '')}")
    if lanes is None:
        lanes = stripe.shape[1]
    if not 0 <= lanes <= stripe.shape[1]:
        raise ValueError(f"lanes={lanes} of a stripe of {stripe.shape[1]}")
    if lanes % VERIFY_BLOCK:
        raise ValueError(f"{lanes} lanes are no whole number of "
                         f"blocks of {VERIFY_BLOCK}")
    m2 = _m2_device(matrix.tobytes(), p, d)
    if device is not None:
        m2 = jax.device_put(m2, device)
    return PendingVerify(
        _submit_slabs(m2, stripe, lanes, device=device, program=_verify_jit),
        p)


def _check_lent(out, o: int, n: int, batch_shape) -> None:
    """Can `out` take the [o, n] result of a 2-D dispatch?"""
    if batch_shape:
        raise ValueError(
            "out= takes the result of a 2-D [S, n] input; this one is "
            f"stacked {batch_shape + ('S', n)}")
    if not isinstance(out, np.ndarray) or out.dtype != np.uint8:
        raise ValueError("out= must be a uint8 numpy array, not "
                         f"{getattr(out, 'dtype', type(out).__name__)}")
    if out.shape != (o, n):
        raise ValueError(f"out= has shape {out.shape}, the result {(o, n)}")
    if not out.flags.writeable or (n > 1 and out.strides[1] != 1):
        raise ValueError("out= must be writable, its rows contiguous")


# Dispatch in fixed, power-of-two lane widths. Every distinct shape costs
# an XLA compile (a second or more each on the chip; util/compile_cache
# persists them), so we bucket: a tail is padded up to the next bucket —
# harmless, since a GF map works lane by lane and the padded lanes are
# simply sliced off.
_MIN_SLAB = 1 << 16   # 64KB
_MAX_SLAB = 1 << 22   # 4MB lanes per dispatch (40MB data for S=10);
                      # value not measured on the attached chip


def _slabs(lanes: int):
    """The bucket rule: yield (want, slab) for a dispatch of `lanes` —
    `want` lanes of it go out as a slab `slab` lanes wide. Whole
    _MAX_SLABs, then one tail in the narrowest power of two from
    _MIN_SLAB up that holds it."""
    while lanes > 0:
        want = min(lanes, _MAX_SLAB)
        slab = _MIN_SLAB
        while slab < want:
            slab <<= 1
        yield want, slab
        lanes -= want


def placed_lanes(lanes: int) -> int:
    """Lanes the slab loop places for a dispatch of `lanes`: a 2-D
    input at least this wide is sliced and never copied."""
    return sum(slab for _, slab in _slabs(lanes))


@functools.lru_cache(maxsize=1)
def _lane_sharding():
    """NamedSharding splitting the lane axis over the devices (None on
    a single-device host). The GF map is per-byte-column, so lane
    sharding is embarrassingly parallel — no collectives — and this
    makes the ordinary service path (volume-server ec.encode ->
    write_ec_files -> apply_matrix) a mesh program on multi-chip hosts
    with no caller changes: XLA partitions the same jitted kernel.

    The mesh takes the largest power-of-two prefix of the device list:
    slab widths are powers of two (>= 2^16), so a power-of-two mesh
    always divides them — a 6-device host shards over 4 rather than
    silently not sharding at all."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    n = 1
    while n * 2 <= len(devs):
        n *= 2
    mesh = Mesh(np.array(devs[:n]), ("lanes",))
    return NamedSharding(mesh, PartitionSpec(None, "lanes"))


def _submit_slabs(m2: jnp.ndarray, flat: np.ndarray, lanes: int, device=None,
                  program=_gf_linear_jit):
    """Issue one async dispatch of `program(m2, slab)` per power-of-two
    slab over the first `lanes` of `flat` [S, >= lanes]; no fetches.

    A tail slab is wider than what it carries. Where `flat` itself
    reaches the slab's end (a staging buffer's slack, handed over with
    the dispatch) the slab is a slice like every other, padded by
    whatever lies there: the map works lane by lane, and every lane
    past `lanes` is dropped from what comes back — a map's rows are
    trimmed to `want`, a verify's counts to want's blocks — so stale
    bytes there reach no result, and nothing is written to zero them.
    Where it does not (a caller that owns no buffer) the tail is copied
    into a fresh zeroed array."""
    s = flat.shape[0]
    sharding = None if device is not None else _lane_sharding()
    parts = []
    pos = 0
    for want, slab in _slabs(lanes):
        on_mesh = sharding is not None and slab % sharding.mesh.size == 0
        with _phase("stage"):
            chunk = flat[:, pos:pos + slab]
            if want < slab:
                if chunk.shape[1] == slab:
                    _TAIL_PAD["in_place"].inc()
                else:
                    padded = np.zeros((s, slab), dtype=np.uint8)
                    padded[:, :want] = chunk[:, :want]
                    chunk = padded
                    _TAIL_PAD["copied"].inc()
            if device is not None or on_mesh:
                chunk = np.ascontiguousarray(chunk)
        # `place` is the time the call holds this thread; nothing here
        # waits for the transfer, so its tail shows up in `wait`. On
        # the default path the strided view goes to jnp.asarray as it
        # is: whatever copy JAX makes of it is inside `place`.
        with _phase("place", bytes=s * slab):
            if device is not None:
                x = jax.device_put(chunk, device)
            elif on_mesh:
                # device_put the HOST array straight onto the sharding:
                # each device receives only its lane slice (going through
                # device 0 first would double the interconnect traffic)
                x = jax.device_put(chunk, sharding)
            else:
                x = jnp.asarray(chunk)
        note_placement(x)
        with _phase("enqueue"):
            res = program(m2, x)
        parts.append((res, want, pos))
        pos += want
    return parts
