"""dist_exchange_mb: megabytes per unit that each chip sends through the
distributed step's all_to_all (graph/distributed.py): iterations x engines
x n_local x the exchange dtype's itemsize, the `exchange_bytes` of the
dist.run spans over 1e6."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["dist.run"], "exchange_bytes", 1e-6)
