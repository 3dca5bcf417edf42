"""Reductions of the counters and timers that the program records as
arguments of its obs spans (`obs.count`, `obs.timer`)."""
from __future__ import annotations

import fnmatch


def arg_per_unit(ctx, patterns, key: str, scale: float = 1.0):
    """The sum of `args[key]` over the window's spans whose name matches one
    of `patterns`, times `scale`, per unit; None where no such span
    recorded that argument."""
    if not ctx.spans:
        return None
    values = [s.args[key] for s in ctx.spans
              if key in s.args and any(fnmatch.fnmatchcase(s.name, p) for p in patterns)]
    if not values:
        return None
    return sum(values) * scale / len(ctx.units)
