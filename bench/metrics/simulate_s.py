"""simulate_s: seconds per answer in batched simulate (experiments/batched.py),
both sweep.simulate passes of an answer summed."""
from bench.spans import per_unit_s


def read(ctx):
    return per_unit_s(ctx, "sweep.simulate")
