"""partition_traffic_s: seconds per answer in partition and traffic
(core/partition.py, core/traffic.py), the sweep.partition_traffic spans."""
from bench.spans import per_unit_s


def read(ctx):
    return per_unit_s(ctx, "sweep.partition_traffic")
