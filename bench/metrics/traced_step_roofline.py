"""traced_step_roofline: the least time the chip needs for the window's
`traced_step` calls (their least bytes, bench/kernels.py, over the HBM
bandwidth of bench/peaks.json) over the device time those calls took.
The step does about one operation per byte, so bandwidth bounds it."""
from bench.kernels import traced_step_bytes

WEIGHTED = {"pagerank": True, "bfs": False}


def _is_step(name: str) -> bool:
    return "_one_iteration" in name


def read(ctx):
    dev = ctx.device
    if dev is None or not ctx.peaks:
        return None
    seconds = dev.module_time_s(_is_step)
    if seconds <= 0:
        return None
    per_answer = ctx.info["iterations"][-len(ctx.units):]
    calls = sum(sum(a.values()) for a in per_answer)
    if dev.module_runs(_is_step) != calls:
        raise RuntimeError(f"the trace holds {dev.module_runs(_is_step)} traced_step runs, the answers {calls}")
    n, e = ctx.info["num_nodes"], ctx.info["num_edges"]
    least = sum(it * traced_step_bytes(n, e, WEIGHTED[alg]) for a in per_answer for alg, it in a.items())
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / seconds
