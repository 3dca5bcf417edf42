"""Reductions of the program's obs spans recorded in a traced window."""
from __future__ import annotations

import fnmatch


def self_time_s(spans, pattern: str) -> float:
    """Seconds of the spans whose name matches `pattern`, less the parts of
    each that spans nested in it on the same thread cover."""
    total = 0
    for s in spans:
        if not fnmatch.fnmatchcase(s.name, pattern):
            continue
        end = s.start_ns + s.dur_ns
        children = sorted(
            (c.start_ns, c.start_ns + c.dur_ns) for c in spans
            if c is not s and c.tid == s.tid and c.start_ns >= s.start_ns
            and c.start_ns + c.dur_ns <= end and c.dur_ns < s.dur_ns
        )
        covered, reach = 0, s.start_ns
        for a, b in children:
            a = max(a, reach)
            if b > a:
                covered += b - a
                reach = b
        total += s.dur_ns - covered
    return total / 1e9


def per_unit_s(ctx, pattern: str):
    """Self time per unit of the window; None where no such span ran."""
    if not ctx.spans or not any(fnmatch.fnmatchcase(s.name, pattern) for s in ctx.spans):
        return None
    return self_time_s(ctx.spans, pattern) / len(ctx.units)

