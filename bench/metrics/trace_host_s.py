"""trace_host_s: seconds per answer of host work in the vertex-program trace
(graph/vertex_program.py, experiments/cache.py): preparing and uploading
the graph, and per iteration the copies to the host and the float64
accumulations, the `host_ns` of the sweep.trace spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["sweep.trace"], "host_ns", 1e-9)
