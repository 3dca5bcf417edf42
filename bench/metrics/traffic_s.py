"""traffic_s: seconds per answer computing traffic matrices (core/traffic.py,
through experiments/cache.py): whole matrices, the blocked shards and their
merge, the `traffic_ns` of the sweep.partition_traffic spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["sweep.partition_traffic"], "traffic_ns", 1e-9)
