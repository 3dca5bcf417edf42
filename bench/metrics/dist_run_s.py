"""dist_run_s: seconds per unit of the distributed engine's queries
(graph/distributed.py:DistributedEngine.run), the self time of the
dist.run spans."""
from bench.spans import per_unit_s


def read(ctx):
    return per_unit_s(ctx, "dist.run")
