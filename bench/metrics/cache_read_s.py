"""cache_read_s: seconds per answer reading the sweep cache from disk
(experiments/cache.py): np.load of traces, traffic and shards, the
`read_ns` of the sweep.trace and sweep.partition_traffic spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["sweep.trace", "sweep.partition_traffic"], "read_ns", 1e-9)
