"""placement_s: seconds per answer in the stacked placement search
(experiments/placement_batch.py), the sweep.placement spans."""
from bench.spans import per_unit_s


def read(ctx):
    return per_unit_s(ctx, "sweep.placement")
