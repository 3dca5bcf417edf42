"""dist_step_roofline: the least time a chip needs for its share of the
window's distributed steps (their least bytes per chip,
bench/kernels_distributed.py, over the HBM bandwidth of bench/peaks.json)
over the device time those steps took on a chip, averaged over the chips.
The step does about one operation per byte, so bandwidth bounds it."""
from bench.kernels_distributed import dist_step_bytes

WEIGHTED = {"pagerank": True, "bfs": False}


def is_step(name: str) -> bool:
    return "dist_step" in name


def read(ctx):
    dev = ctx.device
    if dev is None or not ctx.peaks or ctx.mix["kind"] != "distributed" or not dev.devices:
        return None
    seconds = dev.module_time_s(is_step)
    if seconds <= 0:
        return None
    info = ctx.info
    per_unit = info["iterations"][-len(ctx.units):]
    steps = sum(sum(u.values()) for u in per_unit)
    runs = dev.module_runs(is_step)
    if runs != steps * len(dev.devices):
        raise RuntimeError(f"the trace holds {runs} dist_step runs over {len(dev.devices)} chips, "
                           f"the units {steps} steps")
    least = sum(
        it * dist_step_bytes(info["n_local"], info["e_local"], info["engines"], WEIGHTED[q],
                             info["exchange_itemsize"])
        for u in per_unit for q, it in u.items())
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / seconds
