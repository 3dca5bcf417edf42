"""nocsim_jax_dispatches: device calls per answer of the nocsim arms on jax
(nocsim/batch.py, nocsim/credit.py), one per window chunk, the
`dispatches` of the nocsim.<arm>.jax spans."""
from bench.counters import arg_per_unit


def read(ctx):
    return arg_per_unit(ctx, ["nocsim.*.jax"], "dispatches")
