"""Reader: a statistic over the program's spans (``stats/trace``) of one
name, recorded while the window ran: their mean duration. ``args``:
``span`` — the name; ``scale`` — e.g. 1000 for milliseconds. No such
span: nothing to read.
"""

from __future__ import annotations


def read(ctx: dict, args: dict):
    durs = [d for name, _, d in ctx["spans"] if name == args["span"]]
    if not durs:
        return None
    return args.get("scale", 1.0) * sum(durs) / len(durs)
