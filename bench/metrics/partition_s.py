"""partition_s: seconds per answer in partition_by_name (core/partition.py,
through experiments/cache.py), the `partition_ns` of the
sweep.partition_traffic spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["sweep.partition_traffic"], "partition_ns", 1e-9)
