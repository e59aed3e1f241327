"""Pallas TPU kernel for the GF(2^8) linear map — the fused fast path.

The XLA einsum formulation (rs_kernel.gf_linear) materializes the
8x bit-plane expansion of the data in HBM: per encode it writes+reads
~8x the payload, which pins the measured throughput to roofline/16-ish
(~38 GB/s on v5e) even though the MXU is nearly idle. This kernel
fuses the whole chain per lane tile inside VMEM:

    load data[S, T] (uint8, HBM -> VMEM, pipelined by the grid)
      -> 8 bit-planes (VPU shifts, int8, VMEM only)
      -> 8 small MXU matmuls  acc += M2_j[O8, S] @ bits_j[S, T]
      -> mod-2 + bit-pack (VPU)
    store out[O, T] (uint8)

HBM traffic drops to data-in + parity-out (1.4x payload for RS(10,4)
encode), the compute is exact int8->int32 MXU work, and the grid
pipelines the tiles (guide: "Grid and Block Specifications").

Speed on the attached chip: not measured. The einsum path
(rs_kernel) is the default codec; this kernel is the opt-in
`backend="pallas"`. It compiles for the TPU or raises: interpret mode
exists for tests, which ask for it themselves
(`gf_linear_pallas(..., interpret=True)`), never for the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from seaweedfs_tpu.ops import gf256

# Lanes per grid step. VMEM budget/tile at S=10, O=4:
# data 10T + bits 8*10T + acc 32T*4 + out 4T ~= 222T bytes
# T=32768 -> ~7.3MB, within the ~16MB/core VMEM with double buffering.
TILE = 32768


def _kernel(o8: int, s: int, m2_ref, data_ref, out_ref):
    """One lane tile: expand -> one K=s*8 matmul -> pack.

    m2_ref:   [o8, s*8] int8 — GF(2) bit-matrix, columns plane-major
              (bit j of shard d at column j*s + d, matching the
              concatenated bit-plane layout built below)
    data_ref: [s, T] uint8
    out_ref:  [o8 // 8, T] uint8
    """
    x = data_ref[:]
    # bit planes via mask+compare on i8 (Mosaic has no i8 vector
    # shifts); ONE K=s*8 matmul keeps the MXU fed instead of 8 K=s ones
    planes = [((x & np.uint8(1 << j)) != 0).astype(jnp.int8)
              for j in range(8)]
    bits = jnp.concatenate(planes, axis=0)         # [s*8, T]
    acc = jax.lax.dot_general(
        m2_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                              # [o8, T]
    o = o8 // 8
    for r in range(o):
        row = acc[r * 8, :] & 1
        for k in range(1, 8):
            row = row | ((acc[r * 8 + k, :] & 1) << k)
        out_ref[r, :] = row.astype(jnp.uint8)


@functools.lru_cache(maxsize=64)
def _m2_planes(matrix_bytes: bytes, o: int, s: int) -> np.ndarray:
    """[O*8, S*8] int8 with columns ordered plane-major (bit j of
    shard d at column j*s + d) to match the kernel's concatenated
    bit-plane layout."""
    m = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(o, s)
    m2 = gf256.gf256_matrix_to_gf2(m).astype(np.int8)   # [O*8, S*8]
    out = np.empty_like(m2)
    for j in range(8):
        out[:, j * s:(j + 1) * s] = m2[:, j::8]
    return out


@functools.lru_cache(maxsize=64)
def _build_call(o: int, s: int, n: int, interpret: bool):
    o8 = o * 8
    tile = min(TILE, n)
    if n % tile != 0:
        raise ValueError(f"lane count {n} not a tile multiple")
    grid = (n // tile,)

    kernel = functools.partial(_kernel, o8, s)
    return jax.jit(functools.partial(
        _call, kernel, o, s, n, tile, grid, interpret))


def _call(kernel, o, s, n, tile, grid, interpret, planes, data):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((o, n), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((o * 8, s * 8), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((s, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((o, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(planes, data)


def gf_linear_pallas(matrix: np.ndarray, data, *,
                     interpret: bool = False) -> jax.Array:
    """Apply GF(2^8) matrix [O, S] to data [S, N] uint8 -> [O, N].

    N must be a multiple of 128 (lane tiling) and either <= TILE or a
    multiple of TILE — apply_matrix below slabs arbitrary sizes into
    those shapes (bounded distinct compiles, like rs_kernel's slab
    dispatcher). `interpret=True` runs the Pallas interpreter instead
    of compiling for the TPU — a test's choice, never the program's.
    """
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    o, s = matrix.shape
    data = jnp.asarray(data, dtype=jnp.uint8)
    n = data.shape[-1]
    if n % 128 != 0:
        raise ValueError(f"lane count {n} not a multiple of 128")
    planes = jnp.asarray(_m2_planes(matrix.tobytes(), o, s))
    call = _build_call(o, s, n, interpret)
    return call(planes, data)


def apply_matrix(matrix: np.ndarray, shards) -> np.ndarray:
    """Host-friendly codec entry mirroring rs_kernel.apply_matrix:
    flattens batch dims into lanes and dispatches the Pallas kernel in
    TILE-sized slabs, with the tail padded up to a power-of-two bucket
    — GF maps send 0 to 0, so padding trims cleanly, and the distinct
    compiled shapes stay bounded. Always compiles for the TPU: off the
    chip the Mosaic lowering raises rather than interpret quietly."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    shards = np.asarray(shards, dtype=np.uint8)
    batch_shape = shards.shape[:-2]
    s, lanes = shards.shape[-2:]
    o = matrix.shape[0]
    if batch_shape:
        flat = np.ascontiguousarray(np.moveaxis(
            shards.reshape((-1, s, lanes)), 1, 0)).reshape(s, -1)
    else:
        flat = shards
    n = flat.shape[1]
    if n == 0:
        return np.zeros(batch_shape + (o, lanes), dtype=np.uint8)
    out = np.empty((o, n), dtype=np.uint8)
    pos = 0
    while pos < n:
        want = min(TILE, n - pos)
        chunk = flat[:, pos:pos + want]
        if want < TILE:
            bucket = 128
            while bucket < want:
                bucket <<= 1
            padded = np.zeros((s, bucket), dtype=np.uint8)
            padded[:, :want] = chunk
            chunk = padded
        res = np.asarray(gf_linear_pallas(matrix, chunk))
        out[:, pos:pos + want] = res[:, :want]
        pos += want
    if batch_shape:
        out = np.moveaxis(out.reshape(o, -1, lanes), 0, 1).reshape(
            batch_shape + (o, lanes))
    return out
