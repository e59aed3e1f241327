"""Reader: the movement of the program's own counters over the window.

``args``: ``sum_of`` — series of ``/metrics`` (``name{label="v"}`` as
scraped) whose deltas are added; ``per`` — what the sum is divided by:
``{"work": <key>}`` takes a quantity the traffic driver reports (GiB
done, ...), ``{"sum_of": [...]}`` the delta of further series. Nothing
moved in the divisor: nothing to read.
"""

from __future__ import annotations


def _delta(ctx: dict, series) -> float:
    return sum(ctx["metrics1"].get(s, 0.0) - ctx["metrics0"].get(s, 0.0)
               for s in series)


def read(ctx: dict, args: dict):
    per = args.get("per", {})
    if "work" in per:
        div = ctx["work"].get(per["work"], 0.0)
    elif "sum_of" in per:
        div = _delta(ctx, per["sum_of"])
    else:
        div = 1.0
    if div <= 0:
        return None
    return _delta(ctx, args["sum_of"]) / div
