"""ctypes bindings for the C++ GF(2^8) RS kernel (CPU baseline).

The shared library is a build artifact, never checked in: the first use
in a process builds it from rs_cpu.cpp with `make` on the machine it
runs on (or finds it already newer than its source), then loads it. A
build or load that fails RAISES — with the compiler's own message —
instead of quietly leaving every caller on the pure-Python fallbacks
(numpy GF at a fraction of the speed, a byte-loop CRC32C). The numpy
path stays available to callers that ask for it by name
(`backend="numpy"`); `available()` is the question "can this host use
the native library", asked by `backend="auto"` and the benchmarks.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_PATH = os.path.join(_SRC_DIR, "rs_cpu.cpp")
_LIB_PATH = os.path.join(_SRC_DIR, "librs_cpu.so")
_lib = None
_load_error: "NativeUnavailable | None" = None
_load_lock = threading.Lock()


class NativeUnavailable(RuntimeError):
    """librs_cpu.so could not be built or loaded on this machine."""


def stale() -> bool:
    """True when the library is missing or older than its source."""
    try:
        return os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return not os.path.exists(_LIB_PATH)


def ensure_built() -> str:
    """Build librs_cpu.so from rs_cpu.cpp unless an up-to-date one is
    there. Raises NativeUnavailable with make's output when the build
    tools are missing or the compile fails. Returns the library path."""
    if not stale():
        return _LIB_PATH
    try:
        proc = subprocess.run(
            ["make", "-C", _SRC_DIR, "-s"], timeout=300,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeUnavailable(
            f"cannot build {_LIB_PATH}: running make failed: {e}") from e
    if proc.returncode != 0 or not os.path.exists(_LIB_PATH):
        raise NativeUnavailable(
            f"cannot build {_LIB_PATH}: make exited {proc.returncode}:\n"
            f"{proc.stdout.strip()}")
    return _LIB_PATH


def _load():
    """The loaded library; builds it first if needed. A failure is
    remembered and re-raised on every later call — one loud error per
    caller, not one silent degradation per process."""
    global _lib, _load_error
    if _lib is not None:
        return _lib
    with _load_lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            raise _load_error
        try:
            lib = ctypes.CDLL(ensure_built())
        except NativeUnavailable as e:
            _load_error = e
            raise
        except OSError as e:
            _load_error = NativeUnavailable(
                f"cannot load {_LIB_PATH}: {e}")
            raise _load_error from e
        lib.gf_linear.restype = None
        lib.gf_linear.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),  # matrix [out, k]
            ctypes.c_int,                    # out rows
            ctypes.c_int,                    # k cols
            ctypes.POINTER(ctypes.c_uint8),  # shards [k, n] (contiguous)
            ctypes.POINTER(ctypes.c_uint8),  # out [out, n]
            ctypes.c_longlong,               # n
        ]
        lib.crc32_ieee.restype = ctypes.c_uint32
        lib.crc32_ieee.argtypes = [
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_longlong,
        ]
        lib.crc32c.restype = ctypes.c_uint32
        lib.crc32c.argtypes = lib.crc32_ieee.argtypes
        _lib = lib
    return _lib


def available() -> bool:
    """Can this host use the native library? Builds it on first ask;
    False (never an exception) when it cannot be built or loaded —
    the reason is in load_error()."""
    try:
        _load()
    except NativeUnavailable:
        return False
    return True


def load_error() -> str:
    """Why available() is False ('' when the library loaded)."""
    with _load_lock:
        return str(_load_error) if _load_error is not None else ""


def apply_matrix(matrix: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """matrix [O, K] uint8 x shards [..., K, N] uint8 -> [..., O, N]."""
    lib = _load()
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    o, k = matrix.shape
    if shards.shape[-2] != k:
        raise ValueError(f"shard count {shards.shape[-2]} != matrix cols {k}")
    n = shards.shape[-1]
    batch_shape = shards.shape[:-2]
    flat = shards.reshape((-1, k, n))
    out = np.empty((flat.shape[0], o, n), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    mp = matrix.ctypes.data_as(u8p)
    for b in range(flat.shape[0]):
        lib.gf_linear(
            mp, o, k,
            flat[b].ctypes.data_as(u8p),
            out[b].ctypes.data_as(u8p),
            ctypes.c_longlong(n),
        )
    return out.reshape(batch_shape + (o, n))


def crc32(data, value: int = 0) -> int:
    """IEEE CRC32 (zlib-compatible) of a bytes-like."""
    lib = _load()
    buf = np.frombuffer(memoryview(data), dtype=np.uint8)
    if buf.size == 0:
        return value
    return int(lib.crc32_ieee(
        ctypes.c_uint32(value),
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_longlong(buf.size)))


_U8P = ctypes.POINTER(ctypes.c_uint8)


def crc32c(data, value: int = 0) -> int:
    """Castagnoli CRC32 — the needle checksum flavor."""
    lib = _load()
    # bytes fast path: c_char_p wraps without copying, skipping the
    # numpy round trip (~2x cheaper per call — it's on the per-needle
    # write path)
    if type(data) is not bytes:
        view = memoryview(data)
        if view.readonly or not view.c_contiguous:
            data = bytes(view)
        elif not view.nbytes:
            return value
        else:
            # a writable buffer (bytearray, numpy, a view of either) is
            # read where it lies: the scrub's sweep checks a 4 MiB
            # payload inside its read buffer
            return int(lib.crc32c(
                value, ctypes.byref(ctypes.c_uint8.from_buffer(view)),
                view.nbytes))
    if not data:
        return value
    return int(lib.crc32c(
        value, ctypes.cast(ctypes.c_char_p(data), _U8P), len(data)))
