#!/usr/bin/env python3
"""A run with the timed path broken underneath: ``--fault <name>`` plants
one fault in the PROGRAM (by patching it in this process, never in the
repo) and then drives ``run.py`` as it stands, skipping only its look
for a chip (``--rehearse``). The run has to come out as not correct.

Faults: ``output-byte-altered`` — one byte of every kernel result is
flipped where the dispatch hands it back (a parity byte in the encode
cell; in the read cell the server's CRC check then refuses the needle);
``parity-not-written`` — the encode scheduler drops its parity writes;
``answer-altered`` — the EC read path alters a byte of the needle's
data after its checks, where the answer is produced;
``stored-byte-altered`` — the volume stores every needle with one byte
of its data altered, so the sealed ``.dat``, the encode job's input,
is not what the seed says.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def plant(fault: str) -> None:
    if fault == "output-byte-altered":
        from seaweedfs_tpu.ops import rs_kernel
        orig = rs_kernel.PendingApply.result

        def result(self):
            out = orig(self)
            if out.size:
                out = out.copy()
                out.reshape(-1)[0] ^= 0x40
            return out
        rs_kernel.PendingApply.result = result
    elif fault == "parity-not-written":
        from seaweedfs_tpu.ec import fleet
        fleet._write_parity_span = lambda base, seg: None
    elif fault == "answer-altered":
        from seaweedfs_tpu.ec import ec_volume
        orig_read = ec_volume.EcVolume.read_needle

        def read_needle(self, *a, **kw):
            n = orig_read(self, *a, **kw)
            data = bytearray(n.data)
            data[len(data) // 2] ^= 0x01
            n.data = bytes(data)
            return n
        ec_volume.EcVolume.read_needle = read_needle
    elif fault == "stored-byte-altered":
        from seaweedfs_tpu.storage import volume
        orig_write = volume.Volume.write_needle

        def write_needle(self, n, *a, **kw):
            data = bytearray(n.data)
            data[len(data) // 2] ^= 0x01
            n.data = bytes(data)
            return orig_write(self, n, *a, **kw)
        volume.Volume.write_needle = write_needle
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault = sys.argv[sys.argv.index("--fault") + 1]
    rest = [a for a in sys.argv[1:] if a not in ("--fault", fault)]
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    plant(fault)
    from benchmark import run
    return run.main(rest + ["--rehearse"])


if __name__ == "__main__":
    sys.exit(main())
