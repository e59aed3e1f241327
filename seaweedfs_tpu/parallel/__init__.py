"""Device-mesh parallelism for the EC compute plane.

The reference scales erasure coding across machines with gRPC fan-out
(/root/reference weed/shell/command_ec_encode.go:160-246 spreads 14 shards
round-robin; weed/storage/store_ec.go:322-376 fans goroutines out for
recovery). On TPU the same axes of parallelism map onto a
`jax.sharding.Mesh`:

  dp — volume-batch axis: independent volumes/rows encoded in parallel
       (the reference's "many volumes at once" cron batching).
  sp — lane (byte-stream) axis: one volume's 1GB row split across chips,
       the sequence-parallel analog; GF maps are per-byte-column so this
       axis needs no collectives for encode, and an all-to-all only when
       re-laying-out shards.

Collectives used: psum (cluster-wide parity checksum aggregation, the
integrity check the reference does per-needle with CRC32), ppermute
(on-mesh shard rotation = balancedEcDistribution over ICI instead of
host gRPC).
"""

from seaweedfs_tpu.parallel.mesh import (
    make_mesh,
    sharded_encode,
    ec_pipeline_step,
    rotate_shards,
    round_robin_by_size,
    fleet_write_ec_files_sharded,
)
from seaweedfs_tpu.parallel.mesh_fleet import (
    MeshError,
    MeshDispatchTimeout,
    MeshUnavailable,
    MeshVerifyMismatch,
    mesh_write_ec_files,
    mesh_verify_ec_files,
    mesh_rebuild_ec_files,
    pod_write_ec_files,
    pod_verify_ec_files,
    sharded_reconstruct,
)

__all__ = ["make_mesh", "sharded_encode", "ec_pipeline_step",
           "rotate_shards", "round_robin_by_size",
           "fleet_write_ec_files_sharded",
           "MeshError", "MeshDispatchTimeout", "MeshUnavailable",
           "MeshVerifyMismatch", "mesh_write_ec_files",
           "mesh_verify_ec_files", "mesh_rebuild_ec_files",
           "pod_write_ec_files", "pod_verify_ec_files",
           "sharded_reconstruct"]
