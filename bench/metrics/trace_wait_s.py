"""trace_wait_s: seconds per answer that the vertex-program trace waits on
the chip (graph/vertex_program.py:run_traced): the frontier test and each
traced_step until its outputs are ready, the `wait_ns` of the sweep.trace
spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["sweep.trace"], "wait_ns", 1e-9)
