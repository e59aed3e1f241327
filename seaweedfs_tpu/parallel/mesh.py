"""Mesh construction + pjit-sharded EC compute.

Everything here is shape-static; callers are expected to feed fixed-size
(batch, lanes) buckets — as the host slab dispatcher in
seaweedfs_tpu/ops/rs_kernel.py does for the single-chip path — so the
number of distinct compiles stays bounded.

Sharding layout for an encode batch `data[B, D, N]` on mesh (dp, sp):

    data    : P('dp', None, 'sp')   — volumes over dp, lanes over sp
    m2      : replicated            — the [32, 80] GF(2) parity bit-matrix
    parity  : P('dp', None, 'sp')   — same layout as data

The einsum contracts only the (replicated) shard axis, so encode inserts
zero collectives — each chip's MXU works on its own [B/dp, D, N/sp] slab,
matching the reference's "every server encodes its own volumes" layout
(weed/server/volume_grpc_erasure_coding.go:38-100) but over ICI-connected
chips instead of gRPC-connected hosts.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seaweedfs_tpu.ops.rs_code import ReedSolomon, DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.ops.rs_kernel import gf_linear, m2_bits, parity_m2_bits


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Tuple[str, str] = ("dp", "sp"),
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh over the available devices, factored (dp, sp).

    dp gets the larger factor (volume batches outnumber the lane splits a
    single volume needs); sp gets the largest power-of-two <= sqrt(n).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    sp = 1
    while sp * 2 * sp * 2 <= n and n % (sp * 2) == 0:
        sp *= 2
    dp = n // sp
    dev_array = np.asarray(devices).reshape(dp, sp)
    return Mesh(dev_array, axis_names)


@functools.lru_cache(maxsize=8)
def _sharded_encode_fn(mesh: Mesh):
    data_spec = NamedSharding(mesh, P("dp", None, "sp"))
    rep = NamedSharding(mesh, P())

    @functools.partial(
        jax.jit,
        in_shardings=(rep, data_spec),
        out_shardings=data_spec,
    )
    def encode(m2, data):  # data: [B, D, N] uint8 -> [B, P, N] uint8
        return gf_linear(m2, data)

    return encode


def sharded_encode(mesh: Mesh, data: np.ndarray) -> jax.Array:
    """Encode a [B, D, N] batch of volume rows across the mesh."""
    return _sharded_encode_fn(mesh)(
        parity_m2_bits(), jnp.asarray(data, dtype=jnp.uint8))


@functools.lru_cache(maxsize=32)
def _rotate_fn(mesh: Mesh, shift: int):
    from jax import shard_map

    dp = mesh.shape["dp"]
    perm = [(i, (i + shift) % dp) for i in range(dp)]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P("dp", None, "sp"), out_specs=P("dp", None, "sp"))
    def _rot(x):
        return jax.lax.ppermute(x, axis_name="dp", perm=perm)

    return jax.jit(_rot)


def rotate_shards(mesh: Mesh, shards: jax.Array, shift: int = 1) -> jax.Array:
    """Rotate the dp-placement of shard slabs by `shift` positions.

    On-mesh equivalent of the reference's balancedEcDistribution
    (shell/command_ec_encode.go:248-264): after encode, each chip holds
    the shards of its own volumes; rotating the batch axis over ICI
    redistributes them so no chip keeps all 14 shards of a volume it
    encoded — the placement invariant ec.balance enforces over gRPC.
    """
    return _rotate_fn(mesh, shift % mesh.shape["dp"])(shards)


@functools.lru_cache(maxsize=8)
def _pipeline_step_fn(mesh: Mesh, drop_a: int, drop_b: int):
    """Full EC pipeline step, jitted over the mesh: encode -> lose two
    shards -> rebuild from survivors -> global parity checksum.

    This is the flagship multi-chip program: encode and rebuild are
    sharded matmuls with zero collectives; the checksum is a psum over
    both mesh axes (the cluster-wide integrity scan `volume.check.disk`
    does host-by-host in the reference).
    """
    present = tuple(i for i in range(TOTAL_SHARDS) if i not in (drop_a, drop_b))
    data_spec = NamedSharding(mesh, P("dp", None, "sp"))
    rep = NamedSharding(mesh, P())

    @functools.partial(
        jax.jit,
        in_shardings=(rep, rep, data_spec),
        out_shardings=(data_spec, data_spec, rep),
    )
    def step(enc_m2, dec_m2, data):
        parity = gf_linear(enc_m2, data)                     # [B, P, N]
        full = jnp.concatenate([data, parity], axis=-2)      # [B, D+P, N]
        survivors = full[:, list(present[:DATA_SHARDS]), :]
        rebuilt = gf_linear(dec_m2, survivors)               # [B, 2, N]
        want = full[:, [drop_a, drop_b], :]
        mismatches = jnp.sum(
            (rebuilt != want).astype(jnp.int32))             # psum over dp+sp
        return parity, rebuilt, mismatches

    return step


def ec_pipeline_step(mesh: Mesh, data: np.ndarray,
                     drop: Tuple[int, int] = (3, 11)):
    """Run encode+rebuild+verify on a [B, D, N] batch; returns
    (parity, rebuilt, mismatch_count). mismatch_count must be 0."""
    step = _pipeline_step_fn(mesh, *drop)
    return step(parity_m2_bits(), _decode_bits(drop),
                jnp.asarray(data, dtype=jnp.uint8))


def _decode_bits(drop: Tuple[int, int]):
    rs = ReedSolomon()
    present = tuple(i for i in range(TOTAL_SHARDS) if i not in drop)
    return m2_bits(rs._decode_matrix(present[:DATA_SHARDS], drop))


# -- fleet scheduler sharded over the devices (ec/fleet.py) ------------------

def round_robin_by_size(base_names: Sequence[str],
                        n_shards: int) -> List[List[str]]:
    """Deal volumes to `n_shards` buckets, largest .dat first, each to
    the currently lightest bucket (the sorted round-robin / LPT deal):
    shard byte-loads stay within one volume of each other, so the
    per-device fleet schedulers finish together instead of the fleet
    waiting on one device that drew all the big volumes."""
    sizes = {b: os.path.getsize(b + ".dat") for b in base_names}
    order = sorted(base_names, key=lambda b: (-sizes[b], b))
    buckets: List[List[str]] = [[] for _ in range(max(1, n_shards))]
    loads = [0] * len(buckets)
    for b in order:
        i = loads.index(min(loads))
        buckets[i].append(b)
        loads[i] += sizes[b] or 1  # empty volumes still cost a slot
    return buckets


def fleet_write_ec_files_sharded(base_names: Sequence[str],
                                 devices: Optional[Sequence] = None,
                                 mesh: Optional[Mesh] = None,
                                 backend: str = "jax",
                                 **fleet_kw) -> None:
    """Shard the fleet across the device mesh: ONE fleet scheduler per
    device, each pinning its fused dispatches to its own chip, with the
    volume list dealt round-robin by size so the shards finish
    together. This is the BASELINE "256 volumes pmapped over v5e-8"
    shape expressed as independent per-chip schedulers — encode has no
    cross-volume math, so schedulers share nothing but the disk.

    Host backends get the same volume sharding (per-scheduler reader
    and encode pools still overlap) with no device pinning; their
    default shard count comes from the core count, not jax.devices()
    — a CPU-only host reports one jax device, which would collapse
    the fleet to a single scheduler (and initialize jax for nothing).
    """
    from seaweedfs_tpu.ec import fleet as fleet_mod

    if not base_names:
        return
    if devices is None:
        if backend == "jax":
            devices = (list(mesh.devices.flat) if mesh is not None
                       else jax.devices())
        else:
            # each scheduler runs its own reader/encode/writer pools,
            # so a couple of schedulers saturate a host; scale gently
            devices = [None] * max(1, min(len(base_names),
                                          (os.cpu_count() or 2) // 2))
    shards = [s for s in round_robin_by_size(base_names, len(devices)) if s]
    if backend != "jax":
        devices = [None] * len(shards)
    errors: List[BaseException] = []

    def run(names: List[str], dev) -> None:
        try:
            fleet_mod.fleet_write_ec_files(names, backend=backend,
                                           device=dev, **fleet_kw)
        except BaseException as e:
            errors.append(e)

    # lint: thread-ok(one scheduler thread per device for the whole pass; no request context)
    threads = [threading.Thread(target=run, args=(names, dev),
                                name=f"fleet-shard-{i}")
               for i, (names, dev) in enumerate(zip(shards, devices))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
