"""Precision of the float32 contractions in the stacked jax backends.

Every jax kernel that stands in for a float64 numpy reference answers to it
within a stated contract: `experiments.batched.simulate_batch` and the
credit arm of `nocsim.credit` within 1e-6 relative, the descent of
`experiments.placement_batch` within 1e-3 relative on converged H (its
accept decisions compare f32 deltas).  A TPU runs an f32 dot with default
precision as a single bfloat16 pass, which keeps about three significant
digits; "highest" runs the f32-accurate multi-pass algorithm.  CPU dots are
f32 either way, so this setting changes no CPU result.

Every such contraction passes `precision=DOT_PRECISION`; this module is the
one place that decides it.
"""

DOT_PRECISION = "highest"
