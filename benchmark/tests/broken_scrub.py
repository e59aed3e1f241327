#!/usr/bin/env python3
"""A run of the scrub cell whose program reports clean without reading:
the needle sweep looks at no needle and the stripe verify's readers fill
their lanes with zeros instead of the shard files' bytes (zeros are a
valid stripe, so the device counts nothing). The command still returns,
names every volume and says ``clean``; the device path is still driven.
Every planted sector stays as it was damaged, so the run has to come out
as not correct — by ``sectors_unrepaired`` and ``damage_misreported``,
and by the bytes of the shard files. Drives ``run.py`` as it stands,
skipping only its look for a chip (``--rehearse``).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def plant() -> None:
    from seaweedfs_tpu.ec import fleet
    from seaweedfs_tpu.scrub import scanner

    def read_nothing(base, rows, shard_size, offset, span, width, buf, off):
        for row, _sid in rows:
            buf[row, off:off + width] = 0

    fleet._read_rows_into = read_nothing
    scanner.scan_ec_volume_needles = \
        lambda ecv, **kw: scanner.EcNeedleScan()


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    plant()
    from benchmark import run
    return run.main(sys.argv[1:] + ["--rehearse"])


if __name__ == "__main__":
    sys.exit(main())
