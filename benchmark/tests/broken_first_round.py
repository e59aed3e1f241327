#!/usr/bin/env python3
"""A run of a repair cell whose FIRST round is broken underneath and
whose later rounds are right: one byte of every kernel result is flipped
where the dispatch hands it back, but only while the first timed
``ec.rebuild`` runs (the fault ``output-byte-altered`` of
``broken_run.py``, switched off again by the second command). The files
that round rebuilt are gone from their places when the window ends —
the next loss deleted them — so only a comparison that holds every
round to the reference can come out as not correct. Drives ``run.py``
as it stands, skipping only its look for a chip (``--rehearse``).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def plant() -> None:
    from seaweedfs_tpu.ops import rs_kernel
    from seaweedfs_tpu.shell import Shell
    result, run_command = rs_kernel.PendingApply.result, Shell.run_command
    rebuilds = [0]

    def counting_run_command(self, line, *a, **kw):
        rebuilds[0] += line.startswith("ec.rebuild")
        return run_command(self, line, *a, **kw)

    def altered_in_the_first_round(self):
        out = result(self)
        if rebuilds[0] == 1 and out.size:
            out = out.copy()
            out.reshape(-1)[0] ^= 0x40
        return out

    Shell.run_command = counting_run_command
    rs_kernel.PendingApply.result = altered_in_the_first_round


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    plant()
    from benchmark import run
    return run.main(sys.argv[1:] + ["--rehearse"])


if __name__ == "__main__":
    sys.exit(main())
