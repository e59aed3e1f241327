"""seaweedfs_tpu — a TPU-native distributed object store.

A from-scratch rebuild of the capabilities of SeaweedFS (reference:
/root/reference, Go) designed TPU-first: the warm-storage erasure-coding
pipeline (RS(10,4) over GF(2^8)) runs as a batched bit-matrix multiply on
TPU via JAX/XLA, sharded over a device mesh with `jax.sharding`, while the
cluster services (master / volume server / filer / gateways) are fresh
Python+C++ implementations of the same architecture.

Layer map (mirrors SURVEY.md §1):
  ops/       GF(2^8) math + the JAX RS kernel (the TPU compute path)
  parallel/  device-mesh sharding, streaming host<->HBM pipeline
  storage/   on-disk formats: needle, .idx, superblock, volume engine
  ec/        erasure-coding pipeline: .ec00-.ec13 / .ecx / .ecj, locate math
  master/    topology, volume layout/growth, sequencing, master server
  volume_server/  dataplane HTTP/gRPC server over the storage engine
  filer/     path namespace, chunked-file model, pluggable stores
  gateways/  S3 / WebDAV front-ends over the filer
  shell/     admin commands (ec.encode / ec.rebuild / ec.balance / ...)
  client/    master client (vid->location cache), assign/upload helpers
  utils/     config, http, compression, misc
  native/    C++ hot paths (RS CPU baseline, crc32c) loaded via ctypes
"""

__version__ = "0.1.0"

import os as _os

if _os.environ.get("SEAWEED_SANITIZE"):
    # arm the runtime concurrency sanitizer BEFORE any submodule
    # creates its module-level locks, so they are wrapped too; when
    # the env var is unset this whole block is one dict lookup
    # (test_perf_gates.test_sanitizer_disabled_overhead)
    from seaweedfs_tpu.util import sanitizer as _sanitizer  # noqa: F401
