"""cache_hash_s: seconds per answer that the sweep cache spends hashing
(experiments/cache.py): the graph digest, the partition and activity
hashes and the keys, the `hash_ns` of the sweep.trace and
sweep.partition_traffic spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["sweep.trace", "sweep.partition_traffic"], "hash_ns", 1e-9)
