"""nocsim_jax_s: seconds per answer in the nocsim arms on jax (nocsim/batch.py,
nocsim/credit.py), the nocsim.<arm>.jax spans."""
from bench.spans import per_unit_s


def read(ctx):
    return per_unit_s(ctx, "nocsim.*.jax")
