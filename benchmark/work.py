"""The least work the chip has to do, reckoned from what the harness
itself knows — never from a counter of the program's and never from the
kernel's own shapes, so that it reads the same whatever implements the
map.

A GF(2^8) linear map ``[out_rows, in_rows]`` over ``columns`` byte
columns has to read ``in_rows`` bytes and write ``out_rows`` bytes per
column, and as a GF(2) bit-matrix product costs
``2 * (8 * out_rows) * (8 * in_rows)`` int8 operations per column.

RS(10,4) encode of D ``.dat`` bytes: D/10 columns, 10 in, 4 out ->
1.4 D bytes, 512 D operations. A one-row reconstruct of B lost bytes:
B columns, 10 in, 1 out -> 11 B bytes, 1280 B operations.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDeviceKind(RuntimeError):
    """The device reports a kind peaks.json has no entry for."""


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDeviceKind(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with their "
            "source before benchmarking on it")
    return table[device_kind]


def gf_linear_map(columns: float, in_rows: int, out_rows: int) -> dict:
    return {"bytes": (in_rows + out_rows) * columns,
            "ops": 2 * (8 * out_rows) * (8 * in_rows) * columns}


def least_seconds(work: dict, peak: dict) -> dict:
    """The least time the chip needs for ``work``, and which peak
    bounds it."""
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["ops"] / peak["int8_ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "int8"}
