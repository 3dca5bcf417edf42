"""dist_wait_s: seconds per unit that the distributed engine waits on the
chips (graph/distributed.py:DistributedEngine.run): the frontier test and
each step until its outputs are ready, the `wait_ns` of the dist.run
spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["dist.run"], "wait_ns", 1e-9)
