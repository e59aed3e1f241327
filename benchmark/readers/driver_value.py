"""Reader: a number the traffic driver itself took on the host's clock
over the whole window — one of its end-to-end readings that the manifest
does not hold to a bound, or one of its notes. ``args``: ``value`` — the
name. The driver gave no such number: nothing to read.
"""

from __future__ import annotations


def read(ctx: dict, args: dict):
    value = ctx["driver"].get(args["value"])
    if not isinstance(value, (int, float)) or isinstance(value, bool):
        return None
    return value
