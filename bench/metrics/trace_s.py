"""trace_s: seconds per answer in the vertex-program trace stage
(graph/vertex_program.py), as the self time of the sweep.trace spans."""
from bench.spans import per_unit_s


def read(ctx):
    return per_unit_s(ctx, "sweep.trace")
