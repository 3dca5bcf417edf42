"""nocsim_numpy_s: seconds per answer in the float64 numpy nocsim arms that run
inside every answer, the nocsim.<arm>.numpy spans."""
from bench.spans import per_unit_s


def read(ctx):
    return per_unit_s(ctx, "nocsim.*.numpy")
