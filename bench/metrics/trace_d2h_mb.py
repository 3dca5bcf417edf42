"""trace_d2h_mb: megabytes per answer that the vertex-program trace copies
from the chip to the host (graph/vertex_program.py:run_traced), the
`d2h_bytes` of the sweep.trace spans over 1e6."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["sweep.trace"], "d2h_bytes", 1e-6)
