"""The main path's kernels compile for the real chip, at real widths.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached (`on-chip-measurement` guide §2): what
it refuses here — an unaligned slice, too much fast memory, a program
that cannot be partitioned — would be refused on the chip too. Nothing
runs, so these say nothing about results or times.

All in ONE file, the topology described inside a module-scoped fixture
(never at import, in a skipif, in parametrize or in conftest.py): only
the worker that is handed this file loads the TPU library.
"""

import numpy as np
import pytest

LANES_MIN = 1 << 16      # smallest slab _submit_slabs dispatches
LANES_MAX = 1 << 22      # largest


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _m2(one_chip, rows=4):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((rows * 8, 80), jnp.int8, sharding=one_chip)


def _data(one_chip, lanes):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((10, lanes), jnp.uint8, sharding=one_chip)


def test_described_device_is_the_kind_the_peaks_table_knows(topo):
    """The described chip reports the same device_kind as the attached
    one, and bench.DEVICE_PEAKS is keyed by that string."""
    import bench
    kind = topo.devices[0].device_kind
    assert kind == "TPU v5 lite"
    assert bench.device_peaks(kind)["hbm_gbps"] == 819.0


# matrix rows: 4 = the parity matrix of ec.encode, 2 = the decode matrix
# of ec.rebuild after a loss of two shards (benchmark cell
# node-repair.rebuild), 1 = a scrub's repair of one condemned shard
# (warm-scrub.scrub)
@pytest.mark.parametrize("rows", [4, 2, 1])
@pytest.mark.parametrize("lanes", [LANES_MIN, LANES_MAX])
def test_gf_linear_compiles_for_the_chip(one_chip, lanes, rows):
    import jax
    from seaweedfs_tpu.ops.rs_kernel import gf_linear
    compiled = jax.jit(gf_linear).lower(
        _m2(one_chip, rows), _data(one_chip, lanes)).compile()
    assert compiled is not None
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("lanes", [LANES_MIN, LANES_MAX])
def test_verify_program_compiles_for_the_chip(one_chip, lanes):
    """A scrub's compare-and-count over a placed [14, lanes] stripe:
    what leaves the device is int32 [2, 4, blocks], KB."""
    import jax
    import jax.numpy as jnp
    from seaweedfs_tpu.ops import rs_kernel
    stripe = jax.ShapeDtypeStruct((14, lanes), jnp.uint8, sharding=one_chip)
    compiled = rs_kernel._verify_jit.lower(_m2(one_chip), stripe).compile()
    counts = 2 * 4 * (lanes // rs_kernel.VERIFY_BLOCK) * 4
    assert counts <= compiled.memory_analysis().output_size_in_bytes \
        <= max(counts, 4096)                 # a tile at the least


def test_gf_linear_gemm_compiles_for_the_chip(one_chip):
    import jax
    from seaweedfs_tpu.ops.rs_kernel import gf_linear_gemm
    compiled = jax.jit(gf_linear_gemm).lower(
        _m2(one_chip), _data(one_chip, LANES_MAX)).compile()
    assert compiled is not None


def test_mesh_gf_program_compiles_for_the_2x2_mesh(topo):
    """mesh_fleet's shard_map GF program on a (dp, sp) = (2, 2) Mesh of
    the described devices: matrix replicated, bucket sharded
    P('dp', None, 'sp'), no collective in the program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from seaweedfs_tpu.parallel import mesh_fleet
    from seaweedfs_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=topo.devices)
    assert (mesh.shape["dp"], mesh.shape["sp"]) == (2, 2)
    # one default bucket: bucket_mb / dp per slot, lanes per sp multiple
    span = mesh_fleet.DEFAULT_BUCKET_MB * (1 << 20) // 2 // 10
    lanes = mesh_fleet._lanes_for(span, 2)
    m2 = jax.ShapeDtypeStruct((32, 80), jnp.int8,
                              sharding=NamedSharding(mesh, P()))
    bucket = jax.ShapeDtypeStruct(
        (2, 10, lanes), jnp.uint8,
        sharding=NamedSharding(mesh, P("dp", None, "sp")))
    compiled = mesh_fleet._mesh_gf_fn(mesh).lower(m2, bucket).compile()
    text = compiled.as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    assert np.prod(compiled.output_shardings.mesh.devices.shape) == 4
