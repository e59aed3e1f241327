"""Reader: shares of the traced part of the window, from the profiler's
trace as ``trace_reduce`` reduced it.

``args["stat"]``:

- ``idle_pct`` — 100 x (1 - union of device-op intervals / traced
  window).
- ``roofline_pct`` — 100 x the least time the chip needs for the traced
  part's work / the summed device time of every operation in it. The
  work is reckoned by ``work.gf_linear_map`` from a quantity the harness
  itself knows (``args["work"]``: ``columns_from`` names it among what
  the traffic driver reports for the traced part, ``columns_per_unit``
  scales it to byte columns), never from the program's counters or the
  kernel's shapes.

No trace, no device plane, or no device time: nothing to read — never 0.
"""

from __future__ import annotations

from benchmark import work


def read(ctx: dict, args: dict):
    tr = ctx["trace"]
    if tr is None:
        return None
    if args["stat"] == "idle_pct":
        return 100.0 * tr["idle_share"]
    if args["stat"] == "roofline_pct":
        w = args["work"]
        units = ctx["work"].get("traced", {}).get(w["columns_from"], 0.0)
        if units <= 0 or tr["device_op_s"] <= 0 or ctx["peaks"] is None:
            return None
        least = work.least_seconds(
            work.gf_linear_map(units * w["columns_per_unit"],
                               w["in_rows"], w["out_rows"]), ctx["peaks"])
        return 100.0 * least["seconds"] / tr["device_op_s"]
    raise ValueError(f"device_trace: unknown stat {args['stat']!r}")
