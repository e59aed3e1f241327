"""Reed-Solomon RS(10,4) codec over GF(2^8).

High-level API used by the EC pipeline (seaweedfs_tpu/ec/). The wire/disk
geometry matches the reference (/root/reference
weed/storage/erasure_coding/ec_encoder.go:17-23): 10 data shards + 4 parity
shards, systematic code, Vandermonde-derived coding matrix.

Backends — a backend asked for by name is used or the call raises; only
"auto" chooses:
  - "jax":   bit-matrix matmul on the default JAX backend (the TPU on a
             chip host, the CPU under JAX_PLATFORMS=cpu) — see
             seaweedfs_tpu/ops/rs_kernel.py
  - "numpy": table-gather encoder on host (the plain reference)
  - "native": C++ shared library (seaweedfs_tpu/native), built from
             source on first use; raises if it cannot be built
  - "auto":  native if this host can build it, else numpy — host-side
             work only, never the device

Any subset of >= data_shards surviving shards can reconstruct everything:
the decode map is (coding_matrix restricted to surviving rows)^-1 composed
with the rows we want — still a single GF(2^8) linear map, so rebuild uses
the exact same TPU kernel as encode, just with a different matrix.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

from seaweedfs_tpu.ops import gf256

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS


@functools.lru_cache(maxsize=16)
def coding_matrix(data_shards: int = DATA_SHARDS,
                  total_shards: int = TOTAL_SHARDS) -> np.ndarray:
    m = gf256.rs_coding_matrix(data_shards, total_shards)
    m.setflags(write=False)
    return m


class _Resolved:
    """Already-computed stand-in for PendingApply (sync backends)."""

    def __init__(self, value: np.ndarray):
        self._value = value

    def result(self) -> np.ndarray:
        return self._value


class ReedSolomon:
    def __init__(self, data_shards: int = DATA_SHARDS,
                 parity_shards: int = PARITY_SHARDS,
                 backend: str = "auto"):
        if data_shards <= 0 or parity_shards < 0:
            raise ValueError("bad shard counts")
        if data_shards + parity_shards > 256:
            raise ValueError("too many shards for GF(2^8)")
        if backend not in ("auto", "jax", "numpy", "native"):
            raise ValueError(f"unknown RS backend {backend!r}")
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.matrix = coding_matrix(data_shards, self.total_shards)
        self.backend = backend
        self._decode_cache: dict = {}

    # -- matrix helpers ------------------------------------------------------

    def _decode_matrix(self, present: tuple, wanted: tuple) -> np.ndarray:
        """GF(2^8) map from shards[present] to shards[wanted].

        present: sorted tuple of >= data_shards available shard ids.
        wanted: tuple of shard ids to produce.
        """
        if len(present) < self.data_shards:
            raise ValueError(
                f"need >= {self.data_shards} shards, have {len(present)}")
        key = (present, wanted)
        cached = self._decode_cache.get(key)
        if cached is not None:
            return cached
        sub = self.matrix[list(present[: self.data_shards])]
        inv = gf256.mat_inv(sub)  # data = inv @ present_shards
        want_rows = self.matrix[list(wanted)]  # wanted = want_rows @ data
        m = gf256.mat_mul(want_rows, inv)
        m.setflags(write=False)
        if len(self._decode_cache) < 512:
            self._decode_cache[key] = m
        return m

    # -- linear-map dispatch -------------------------------------------------

    def _apply(self, matrix: np.ndarray, shards: np.ndarray) -> np.ndarray:
        if self.backend == "jax":
            from seaweedfs_tpu.ops import rs_kernel
            return rs_kernel.apply_matrix(matrix, shards)
        if self.backend in ("auto", "native"):
            from seaweedfs_tpu.native import rs_native
            # asked for by name, a library that cannot be built raises
            # (NativeUnavailable); only "auto" may settle for numpy
            if self.backend == "native" or rs_native.available():
                return rs_native.apply_matrix(matrix, shards)
        return gf256.gf_linear_numpy(matrix, shards)

    # -- public API ----------------------------------------------------------

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data: [..., D, N] uint8 -> parity [..., P, N] uint8."""
        data = np.asarray(data, dtype=np.uint8)
        if data.shape[-2] != self.data_shards:
            raise ValueError(f"expected {self.data_shards} data shards")
        return self._apply(self.matrix[self.data_shards:], data)

    def encode_async(self, data: np.ndarray, device=None, out=None,
                     lanes=None):
        """Pipelined encode: returns a handle with .result() -> parity.

        On the jax backend the dispatch is issued immediately and the
        device computes while the caller does host IO; other backends
        compute synchronously and return a pre-resolved handle, so
        pipeline-structured callers work uniformly. `device` pins the
        dispatch to one jax device (the fleet scheduler runs one
        scheduler per device); `out` lends the result its memory and
        `lanes` says how many lanes of a wider `data` are the dispatch
        (rs_kernel.apply_matrix_async). Host backends ignore `device`
        and `out` (the codec allocates its own result) and map the
        first `lanes`.
        """
        data = np.asarray(data, dtype=np.uint8)
        if data.shape[-2] != self.data_shards:
            raise ValueError(f"expected {self.data_shards} data shards")
        if self.backend == "jax":
            from seaweedfs_tpu.ops import rs_kernel
            return rs_kernel.apply_matrix_async(
                self.matrix[self.data_shards:], data, device=device,
                out=out, lanes=lanes)
        return _Resolved(self._apply(self.matrix[self.data_shards:],
                                     data[..., :lanes]))

    def encode_all(self, data: np.ndarray) -> np.ndarray:
        """data: [..., D, N] -> all shards [..., D+P, N]."""
        parity = self.encode(data)
        return np.concatenate([np.asarray(data, dtype=np.uint8), parity], axis=-2)

    def verify(self, shards: np.ndarray) -> bool:
        """shards: [..., D+P, N]; True iff parity matches data."""
        shards = np.asarray(shards, dtype=np.uint8)
        if shards.shape[-2] != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shards")
        parity = self.encode(shards[..., : self.data_shards, :])
        return bool(np.array_equal(parity, shards[..., self.data_shards:, :]))

    def decode_matrix(self, present: Sequence[int],
                      wanted: Sequence[int]) -> np.ndarray:
        """Public accessor for the GF(2^8) map shards[present[:D]] ->
        shards[wanted] (read-only). The rebuild benchmark feeds this to
        the TPU kernel directly — rebuild is the SAME bit-matmul as
        encode, just a Cauchy-inverse-derived matrix."""
        return self._decode_matrix(tuple(present)[: self.data_shards],
                                   tuple(wanted))

    def reconstruct_some(self, present: Sequence[int], wanted: Sequence[int],
                         shard_data: np.ndarray) -> np.ndarray:
        """Compute shards `wanted` from shards `present`.

        shard_data: [..., len(present), N] uint8, rows ordered like `present`.
        Uses only the first `data_shards` entries of `present`.
        """
        return self.reconstruct_some_async(present, wanted,
                                           shard_data).result()

    def reconstruct_some_async(self, present: Sequence[int],
                               wanted: Sequence[int],
                               shard_data: np.ndarray, device=None,
                               out=None, lanes=None):
        """Pipelined reconstruct_some: returns a handle with .result().

        Same contract as encode_async (`device`, `out`, `lanes`) — on
        the jax backend the dispatch is in flight while the caller
        overlaps host IO (the rebuild pipelines in ec/encoder.py and
        ec/fleet.py ride this)."""
        present = tuple(present)
        m = self._decode_matrix(present[: self.data_shards], tuple(wanted))
        shard_data = np.asarray(shard_data, dtype=np.uint8)
        if self.backend == "jax":
            from seaweedfs_tpu.ops import rs_kernel
            return rs_kernel.apply_matrix_async(
                m, shard_data[..., : self.data_shards, :], device=device,
                out=out, lanes=lanes)
        return _Resolved(self._apply(
            m, shard_data[..., : self.data_shards, :lanes]))

    def reconstruct(self, shards: list[Optional[np.ndarray]],
                    data_only: bool = False) -> list[np.ndarray]:
        """Fill in the missing (None) entries of a full shard list in place.

        Mirrors the reference Reconstruct/ReconstructData semantics
        (ec_encoder.go:233-287, store_ec.go:322-376).
        """
        if len(shards) != self.total_shards:
            raise ValueError(f"expected list of {self.total_shards}")
        present = [i for i, s in enumerate(shards) if s is not None]
        limit = self.data_shards if data_only else self.total_shards
        missing = [i for i in range(limit) if shards[i] is None]
        if not missing:
            return shards
        if len(present) < self.data_shards:
            raise ValueError(
                f"unrecoverable: only {len(present)} of {self.data_shards} "
                "required shards present")
        src = np.stack([np.asarray(shards[i], dtype=np.uint8)
                        for i in present[: self.data_shards]], axis=-2)
        out = self.reconstruct_some(present, missing, src)
        for row, idx in enumerate(missing):
            shards[idx] = np.ascontiguousarray(out[..., row, :])
        return shards
