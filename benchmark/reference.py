"""The plain reference: RS(10,4) over GF(2^8) and the EC striping, in numpy.

The yardstick ``correct`` is decided against. It imports nothing of the
program and takes nothing the program made: the field tables, the
coding matrix, the striping of a ``.dat`` into 14 shard files and the
location of a needle's bytes on those shards are all worked out here.

Field: GF(2^8), polynomial x^8+x^4+x^3+x^2+1 (0x11D), generator 2; the
coding matrix is a 14x10 Vandermonde matrix normalised by the inverse
of its top square, so the top is the identity (the code is systematic)
and the bottom four rows are the parity map — the construction of the
upstream project's RS library (klauspost/reedsolomon), which is what
makes shard files interoperable.

Striping (upstream ``weed/storage/erasure_coding/ec_encoder.go``):
while MORE than 10 large blocks remain, one row gives shard ``i`` the
large block ``i``; the rest is striped the same way in small blocks,
the last row padded with zeros. Shard file ``i`` is its large blocks,
then its small blocks.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS
PRIM_POLY = 0x11D


def _tables() -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= PRIM_POLY
    exp[255:510] = exp[:255]
    a = np.arange(256)
    mul = exp[(log[a][:, None] + log[a][None, :]) % 255].astype(np.uint8)
    mul[0, :] = 0
    mul[:, 0] = 0
    return exp, log, mul


EXP, LOG, MUL = _tables()


def gf_mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[(255 - int(LOG[a])) % 255])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(int(LOG[a]) * n) % 255])


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for k in range(a.shape[1]):
                acc ^= gf_mul(int(a[i, k]), int(b[k, j]))
            out[i, j] = acc
    return out


def mat_inv(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); ValueError when singular."""
    n = m.shape[0]
    w = np.concatenate([np.asarray(m, dtype=np.uint8),
                        np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if w[r, col]), None)
        if pivot is None:
            raise ValueError("singular over GF(2^8)")
        if pivot != col:
            w[[col, pivot]] = w[[pivot, col]]
        w[col] = MUL[gf_inv(int(w[col, col]))][w[col]]
        for r in range(n):
            if r != col and w[r, col]:
                w[r] ^= MUL[int(w[r, col])][w[col]]
    return w[:, n:].copy()


def coding_matrix() -> np.ndarray:
    """[14, 10]: identity on top, the four parity rows below."""
    vm = np.array([[gf_pow(r, c) for c in range(DATA_SHARDS)]
                   for r in range(TOTAL_SHARDS)], dtype=np.uint8)
    return mat_mul(vm, mat_inv(vm[:DATA_SHARDS]))


def parity_matrix() -> np.ndarray:
    return coding_matrix()[DATA_SHARDS:]


def decode_matrix(present: Sequence[int], wanted: Sequence[int]) -> np.ndarray:
    """The map from shards ``present[:10]`` to shards ``wanted``."""
    full = coding_matrix()
    inv = mat_inv(full[list(present[:DATA_SHARDS])])
    return mat_mul(full[list(wanted)], inv)


def apply(matrix: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """matrix [O, K] applied to rows [K, N] of bytes -> [O, N]."""
    matrix = np.asarray(matrix, dtype=np.uint8)
    out = np.zeros((matrix.shape[0], rows.shape[1]), dtype=np.uint8)
    for o in range(matrix.shape[0]):
        for k in range(matrix.shape[1]):
            c = int(matrix[o, k])
            if c:
                out[o] ^= MUL[c][rows[k]]
    return out


# -- striping -----------------------------------------------------------------

def layout(dat_size: int, large_block: int, small_block: int):
    """(large rows, small rows, shard file size) of one volume."""
    large_row = large_block * DATA_SHARDS
    n_large = 0
    remaining = dat_size
    while remaining > large_row:
        n_large += 1
        remaining -= large_row
    n_small = -(-remaining // (small_block * DATA_SHARDS))
    return n_large, n_small, n_large * large_block + n_small * small_block


def _rows(dat_size: int, large_block: int, small_block: int):
    """Every row as (offset in .dat, block size, offset in a shard)."""
    n_large, n_small, _ = layout(dat_size, large_block, small_block)
    for r in range(n_large):
        yield r * large_block * DATA_SHARDS, large_block, r * large_block
    base = n_large * large_block * DATA_SHARDS
    for r in range(n_small):
        yield (base + r * small_block * DATA_SHARDS, small_block,
               n_large * large_block + r * small_block)


def _read_padded(f, offset: int, length: int) -> np.ndarray:
    buf = np.zeros(length, dtype=np.uint8)
    f.seek(offset)
    f.readinto(memoryview(buf))
    return buf


def _spans(dat_path: str, rows, matrix: np.ndarray, piece: int = 1 << 20):
    """Yield ``(offset in a shard file, [14, n] bytes)`` over ``rows``:
    what each of the 14 shard files has to hold there."""
    with open(dat_path, "rb") as f:
        for dat_off, block, shard_off in rows:
            for c in range(0, block, piece):
                n = min(piece, block - c)
                data = np.stack([
                    _read_padded(f, dat_off + i * block + c, n)
                    for i in range(DATA_SHARDS)])
                yield shard_off + c, np.concatenate(
                    [data, apply(matrix, data)])


def _compare_rows(dat_path: str, shard_paths: Sequence[str], matrix,
                  rows) -> int:
    differing = 0
    files = [open(p, "rb") for p in shard_paths]
    try:
        for off, want in _spans(dat_path, rows, matrix):
            n = want.shape[1]
            for sid, sf in enumerate(files):
                sf.seek(off)
                got = np.frombuffer(sf.read(n), dtype=np.uint8)
                short = n - got.size          # a short file differs there
                differing += short + int(np.count_nonzero(
                    got != want[sid, :got.size]))
    finally:
        for sf in files:
            sf.close()
    return differing


def compare_volume(dat_path: str, shard_paths: Sequence[str],
                   large_block: int, small_block: int,
                   matrix: np.ndarray | None = None,
                   threads: int = 1) -> Dict[str, int]:
    """Bytes of the 14 shard files that differ from what the reference
    says they hold (missing bytes and surplus bytes count as differing),
    and how many were compared. A missing file differs in every byte."""
    matrix = parity_matrix() if matrix is None else matrix
    dat_size = os.path.getsize(dat_path)
    _, _, shard_size = layout(dat_size, large_block, small_block)
    differing = 0
    present = []
    for p in shard_paths:
        if not os.path.exists(p):
            differing += shard_size
            present.append(None)
            continue
        present.append(p)
        differing += max(0, os.path.getsize(p) - shard_size)   # surplus
    if any(p is None for p in present):
        return {"differing": differing,
                "compared": shard_size * TOTAL_SHARDS}
    rows = list(_rows(dat_size, large_block, small_block))
    threads = max(1, min(threads, len(rows)))
    parts = [rows[i::threads] for i in range(threads)]
    with ThreadPoolExecutor(threads) as pool:
        differing += sum(pool.map(lambda part: _compare_rows(
            dat_path, shard_paths, matrix, part), parts))
    return {"differing": differing, "compared": shard_size * TOTAL_SHARDS}


def write_shards(dat_path: str, shard_paths: Sequence[str], large_block: int,
                 small_block: int, matrix: np.ndarray | None = None) -> None:
    """The reference put in the program's place: write the 14 shard
    files of one volume (used by the controls and the tests)."""
    matrix = parity_matrix() if matrix is None else matrix
    rows = _rows(os.path.getsize(dat_path), large_block, small_block)
    files = [open(p, "wb") for p in shard_paths]
    try:
        for off, span in _spans(dat_path, rows, matrix):
            for sid, sf in enumerate(files):
                sf.seek(off)
                sf.write(span[sid].tobytes())
    finally:
        for sf in files:
            sf.close()


# -- where a needle's bytes lie ----------------------------------------------

# a needle's record in the .dat: header (cookie 4, id 8, size 4), then
# the data's length (4), then the data
DATA_OFFSET_IN_RECORD = 20


def fid_key(fid: str) -> int:
    """The needle's key in a file id ``<volume>,<key hex><cookie, 8 hex>``."""
    return int(fid.split(",")[1][:-8], 16)


def needle_records(idx_path: str, dat_size: int) -> Dict[int, Tuple[int, int]]:
    """key -> (offset, length) of each needle's record in the ``.dat``,
    from the volume's ``.idx`` (upstream's format: 16-byte entries, key
    8, offset 4 in units of 8 bytes, size 4, big-endian)."""
    raw = np.fromfile(idx_path, dtype=np.uint8)
    raw = raw[:raw.size - raw.size % 16].reshape(-1, 16)
    keys = raw[:, :8].copy().view(">u8").reshape(-1)
    offs = raw[:, 8:12].copy().view(">u4").reshape(-1).astype(np.int64) * 8
    order = np.argsort(offs)
    ends = np.append(offs[order][1:], dat_size)
    return {int(keys[j]): (int(offs[j]), int(e - offs[j]))
            for j, e in zip(order, ends)}


def locate(dat_size: int, large_block: int, small_block: int, offset: int,
           size: int) -> List[Tuple[int, int, int]]:
    """``dat[offset:offset+size]`` as ``(shard id, offset in the shard
    file, bytes)`` pieces, in order."""
    n_large, _, _ = layout(dat_size, large_block, small_block)
    large_row = large_block * DATA_SHARDS
    out = []
    while size > 0:
        if offset < n_large * large_row:
            block, row = large_block, offset // large_row
            inner = offset - row * large_row
            shard_base = row * large_block
        else:
            rel = offset - n_large * large_row
            block, row = small_block, rel // (small_block * DATA_SHARDS)
            inner = rel - row * small_block * DATA_SHARDS
            shard_base = n_large * large_block + row * small_block
        sid, within = divmod(inner, block)
        take = min(size, block - within)
        out.append((sid, shard_base + within, take))
        offset += take
        size -= take
    return out


def bytes_on(pieces: Iterable[Tuple[int, int, int]],
             shards: Iterable[int]) -> int:
    lost = set(shards)
    return sum(n for sid, _, n in pieces if sid in lost)
