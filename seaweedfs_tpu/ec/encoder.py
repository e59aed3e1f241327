"""EC encode / rebuild / decode over volume files.

Equivalent behavior to reference weed/storage/erasure_coding/
ec_encoder.go + ec_decoder.go, re-structured for TPU batch compute:

The reference encodes serially in 256KB batches through a per-volume Go
loop. Here each 10-block row is encoded as a [10, chunk] uint8 matrix and
parity comes from one GF(2^8) linear map (seaweedfs_tpu/ops) — on TPU
a single MXU matmul per chunk, with `chunk` sized in the tens of MB so
dispatch latency amortizes. Data shards never pass through the RS path
at all: they are straight padded copies of .dat slices (the code is
systematic), halving the IO the reference's buffer loop does.
"""

from __future__ import annotations

import os
from collections import deque
from typing import Deque, List, Optional, Tuple

import numpy as np

from seaweedfs_tpu.ops.rs_code import ReedSolomon, DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.storage import idx as idx_codec
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import actual_size

LARGE_BLOCK_SIZE = 1 << 30  # 1GB
SMALL_BLOCK_SIZE = 1 << 20  # 1MB
DEFAULT_CHUNK = 16 << 20      # RS dispatch granularity, host backends
DEFAULT_CHUNK_JAX = 128 << 20  # jax: larger batches amortize dispatch
                               # (value not measured on the attached chip)


def shard_file_name(base_name: str, shard_id: int) -> str:
    return f"{base_name}.ec{shard_id:02d}"


def _rs(backend: str) -> ReedSolomon:
    return ReedSolomon(backend=backend)


# --- encode -----------------------------------------------------------------

def default_chunk_for(backend: str) -> int:
    """Per-backend RS dispatch granularity: the jax path needs large
    batches to amortize dispatch latency; host backends prefer
    cache-sized chunks."""
    return DEFAULT_CHUNK_JAX if backend == "jax" else DEFAULT_CHUNK


def write_ec_files(base_name: str, backend: str = "auto",
                   large_block: int = LARGE_BLOCK_SIZE,
                   small_block: int = SMALL_BLOCK_SIZE,
                   chunk: Optional[int] = None) -> None:
    """Generate .ec00-.ec13 from <base>.dat.

    Rows are consumed exactly like the reference encoder
    (ec_encoder.go:194-231): large rows while MORE than 10*large_block
    remains, then zero-padded small rows.
    """
    if chunk is None:
        chunk = default_chunk_for(backend)
    rs = _rs(backend)
    dat_path = base_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    outputs = [open(shard_file_name(base_name, i), "wb")
               for i in range(TOTAL_SHARDS)]
    pipe = _EncodePipeline()
    try:
        with open(dat_path, "rb") as dat:
            remaining = dat_size
            processed = 0
            while remaining > large_block * DATA_SHARDS:
                _encode_large_row(rs, dat, processed, large_block, outputs,
                                  chunk, pipe)
                remaining -= large_block * DATA_SHARDS
                processed += large_block * DATA_SHARDS
            if remaining > 0:
                n_rows = -(-remaining // (small_block * DATA_SHARDS))
                _encode_small_rows(rs, dat, processed, small_block, n_rows,
                                   outputs, chunk, pipe)
        pipe.drain()
    finally:
        for f in outputs:
            f.close()


def _read_padded(f, offset: int, length: int) -> np.ndarray:
    """One buffer filled in place: readinto() avoids the
    frombuffer+concatenate double allocation on tail chunks, only the
    EOF tail is memset, and the result is writable (a read-only
    frombuffer view forces copies downstream)."""
    buf = np.empty(length, dtype=np.uint8)
    f.seek(offset)
    got = f.readinto(memoryview(buf))
    if got < length:
        buf[got:] = 0  # zero padding past EOF
    return buf


# How many encode dispatches may be in flight at once. Depth 2 is classic
# double buffering: while the device computes parity for chunk i, the host
# writes chunk i-1's shards and reads chunk i+1 from disk (SURVEY §7
# "overlap gRPC ingest, host staging, device_put and compute").
PIPELINE_DEPTH = 2


class _EncodePipeline:
    """Bounded in-flight queue of (data, pending-parity, writeback)."""

    def __init__(self, depth: int = PIPELINE_DEPTH):
        self._inflight: Deque[Tuple] = deque()
        self._depth = max(1, depth)

    def submit(self, handle, writeback) -> None:
        self._inflight.append((handle, writeback))
        while len(self._inflight) >= self._depth:
            self._retire_one()

    def _retire_one(self) -> None:
        handle, writeback = self._inflight.popleft()
        writeback(handle.result())

    def drain(self) -> None:
        while self._inflight:
            self._retire_one()


def _encode_large_row(rs: ReedSolomon, dat, row_offset: int, block_size: int,
                      outputs: List, chunk: int,
                      pipe: Optional[_EncodePipeline] = None) -> None:
    """One large row: shard i gets dat[row_offset + i*block : +block]
    (padded); parity comes chunk-at-a-time so a 1GB row never needs 10GB
    resident. Data shards are written immediately (the code is
    systematic); parity writes retire through the pipeline so device
    compute overlaps the next chunk's disk read."""
    own = pipe is None
    pipe = pipe or _EncodePipeline()
    for c in range(0, block_size, chunk):
        clen = min(chunk, block_size - c)
        data = np.empty((DATA_SHARDS, clen), dtype=np.uint8)
        for i in range(DATA_SHARDS):
            data[i] = _read_padded(dat, row_offset + i * block_size + c, clen)
        handle = rs.encode_async(data)
        for i in range(DATA_SHARDS):
            outputs[i].write(data[i].tobytes())

        def write_parity(parity, outputs=outputs):
            for p in range(parity.shape[0]):
                outputs[DATA_SHARDS + p].write(parity[p].tobytes())

        pipe.submit(handle, write_parity)
    if own:
        pipe.drain()


def _encode_small_rows(rs: ReedSolomon, dat, start_offset: int,
                       small_block: int, n_rows: int, outputs: List,
                       chunk: int,
                       pipe: Optional[_EncodePipeline] = None) -> None:
    """Tail small rows, batched: consecutive rows are contiguous in the
    .dat, so a span of B rows is just a reshape to [B, 10, small] and
    parity for all of them is ONE RS dispatch — this is what amortizes
    TPU dispatch latency (vs the reference's serial 256KB loop)."""
    own = pipe is None
    pipe = pipe or _EncodePipeline()
    rows_per_batch = max(1, chunk // (small_block * DATA_SHARDS))
    row_bytes = small_block * DATA_SHARDS
    for r0 in range(0, n_rows, rows_per_batch):
        rows = min(rows_per_batch, n_rows - r0)
        span = _read_padded(dat, start_offset + r0 * row_bytes,
                            rows * row_bytes)
        data = span.reshape(rows, DATA_SHARDS, small_block)
        handle = rs.encode_async(data)
        for i in range(DATA_SHARDS):
            outputs[i].write(np.ascontiguousarray(data[:, i, :]).tobytes())

        def write_parity(parity, outputs=outputs):
            for p in range(parity.shape[1]):
                outputs[DATA_SHARDS + p].write(
                    np.ascontiguousarray(parity[:, p, :]).tobytes())

        pipe.submit(handle, write_parity)
    if own:
        pipe.drain()


def write_sorted_file_from_idx(base_name: str, ext: str = ".ecx") -> None:
    """Replay <base>.idx, write the *live* needle set key-sorted as .ecx.

    Matches reference WriteSortedFileFromIdx (ec_encoder.go:27-54): the
    final state per key (tombstones applied) sorted ascending.
    """
    with open(base_name + ".idx", "rb") as f:
        arr = idx_codec.parse_index_bytes(f.read())
    final: dict[int, tuple[int, int]] = {}
    for i in range(len(arr)):
        key = int(arr["key"][i])
        size = int(arr["size"][i])
        if t.size_is_deleted(size):
            final.pop(key, None)
        else:
            final[key] = (int(arr["offset"][i]), size)
    with open(base_name + ext, "wb") as out:
        for key in sorted(final):
            offset, size = final[key]
            out.write(idx_codec.entry_to_bytes(key, offset, size))


# --- rebuild ----------------------------------------------------------------

def rebuild_ec_files(base_name: str, backend: str = "auto",
                     chunk: Optional[int] = None,
                     wanted: Optional[List[int]] = None) -> List[int]:
    """Regenerate missing .ecNN from >=10 present ones.

    `wanted` restricts which missing shards get rebuilt (decode-to-volume
    only needs the data shards). Returns the generated shard ids
    (reference generateMissingEcFiles, ec_encoder.go:88-118).
    """
    if chunk is None:
        chunk = default_chunk_for(backend)
    rs = _rs(backend)
    present = [i for i in range(TOTAL_SHARDS)
               if os.path.exists(shard_file_name(base_name, i))]
    missing = [i for i in (range(TOTAL_SHARDS) if wanted is None else wanted)
               if i not in present]
    if not missing:
        return []
    if len(present) < DATA_SHARDS:
        raise ValueError(
            f"cannot rebuild: only {len(present)} shards present")
    shard_size = os.path.getsize(shard_file_name(base_name, present[0]))
    ins = {i: open(shard_file_name(base_name, i), "rb") for i in present}
    outs = {i: open(shard_file_name(base_name, i), "wb") for i in missing}
    pipe = _EncodePipeline()
    try:
        for c in range(0, shard_size, chunk):
            clen = min(chunk, shard_size - c)
            src = np.empty((len(present[:DATA_SHARDS]), clen), dtype=np.uint8)
            for row, i in enumerate(present[:DATA_SHARDS]):
                src[row] = _read_padded(ins[i], c, clen)
            handle = rs.reconstruct_some_async(present, missing, src)

            def write_rebuilt(out, outs=outs):
                for row, i in enumerate(missing):
                    outs[i].write(out[row].tobytes())

            # retire in FIFO order: while the device reconstructs chunk
            # i, the host reads chunk i+1 (same overlap as encode)
            pipe.submit(handle, write_rebuilt)
        pipe.drain()
    finally:
        for f in ins.values():
            f.close()
        for f in outs.values():
            f.close()
    return missing


# --- decode back to a volume ------------------------------------------------

def _read_ec_volume_version(base_name: str) -> int:
    """The original superblock lives in the first bytes of .ec00."""
    with open(shard_file_name(base_name, 0), "rb") as f:
        header = f.read(8)
    if len(header) < 8:
        raise ValueError("ec00 shard too short for a superblock")
    return header[0]


def find_dat_file_size(base_name: str, index_base_name: Optional[str] = None) -> int:
    """Recover the original .dat size from the max .ecx entry end.

    (reference ec_decoder.go:45-70; trailing deletes past the max entry
    are deletions anyway.)
    """
    version = _read_ec_volume_version(base_name)
    index_base_name = index_base_name or base_name
    with open(index_base_name + ".ecx", "rb") as f:
        arr = idx_codec.parse_index_bytes(f.read())
    dat_size = 8  # at least the superblock
    for i in range(len(arr)):
        size = int(arr["size"][i])
        if t.size_is_deleted(size):
            continue
        end = int(arr["offset"][i]) + actual_size(size, version)
        dat_size = max(dat_size, end)
    return dat_size


def write_dat_file(base_name: str, dat_size: int,
                   large_block: int = LARGE_BLOCK_SIZE,
                   small_block: int = SMALL_BLOCK_SIZE,
                   chunk: Optional[int] = None,
                   backend: str = "auto") -> None:
    """Re-interleave .ec00-.ec09 rows back into <base>.dat
    (reference WriteDatFile, ec_decoder.go:153-195). The chunk default
    follows the backend like encode/rebuild do."""
    if chunk is None:
        chunk = default_chunk_for(backend)
    inputs = [open(shard_file_name(base_name, i), "rb")
              for i in range(DATA_SHARDS)]
    try:
        with open(base_name + ".dat", "wb") as dat:
            shard_off = 0
            remaining = dat_size
            while remaining > large_block * DATA_SHARDS:
                _decode_row(inputs, dat, shard_off, large_block, chunk)
                shard_off += large_block
                remaining -= large_block * DATA_SHARDS
            while remaining > 0:
                _decode_row(inputs, dat, shard_off, small_block, chunk)
                shard_off += small_block
                remaining -= small_block * DATA_SHARDS
            dat.truncate(dat_size)
    finally:
        for f in inputs:
            f.close()


def _decode_row(inputs: List, dat, shard_off: int, block_size: int,
                chunk: int) -> None:
    for i in range(DATA_SHARDS):
        for c in range(0, block_size, chunk):
            clen = min(chunk, block_size - c)
            buf = _read_padded(inputs[i], shard_off + c, clen)
            dat.write(buf.tobytes())


def rebuild_ecx_file(base_name: str) -> None:
    """Replay the .ecj journal into the sorted .ecx (tombstone in place),
    then drop the journal (reference RebuildEcxFile,
    ec_volume_delete.go:51-98)."""
    ecj_path = base_name + ".ecj"
    if not os.path.exists(ecj_path):
        return
    with open(base_name + ".ecx", "r+b") as ecx:
        arr = None
        with open(ecj_path, "rb") as j:
            journal = j.read()
        if journal:
            ecx.seek(0)
            arr = idx_codec.parse_index_bytes(ecx.read())
        for jo in range(0, len(journal) - len(journal) % 8, 8):
            key = int.from_bytes(journal[jo:jo + 8], "big")
            i = int(np.searchsorted(arr["key"], np.uint64(key)))
            if i < len(arr) and int(arr["key"][i]) == key:
                ecx.seek(i * t.NEEDLE_MAP_ENTRY_SIZE + t.NEEDLE_ID_SIZE + t.OFFSET_SIZE)
                ecx.write((t.TOMBSTONE_SIZE & 0xFFFFFFFF).to_bytes(4, "big"))
    os.remove(ecj_path)


def write_idx_file_from_ec_index(base_name: str) -> None:
    """.idx = .ecx copied + tombstone entries for every .ecj id
    (reference WriteIdxFileFromEcIndex, ec_decoder.go:18-43)."""
    with open(base_name + ".ecx", "rb") as f:
        ecx = f.read()
    with open(base_name + ".idx", "wb") as out:
        out.write(ecx)
        ecj_path = base_name + ".ecj"
        if os.path.exists(ecj_path):
            with open(ecj_path, "rb") as j:
                while True:
                    b = j.read(t.NEEDLE_ID_SIZE)
                    if len(b) < t.NEEDLE_ID_SIZE:
                        break
                    key = int.from_bytes(b, "big")
                    out.write(idx_codec.entry_to_bytes(key, 0, t.TOMBSTONE_SIZE))
