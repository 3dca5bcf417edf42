"""The benchmark's own R-MAT generator (Chakrabarti et al., SDM'04), on the device.

The distribution is the repository's `graph/generators.py:rmat`: every edge
picks one of four quadrants at each of ceil(log2 |V|) levels with
probabilities A/B/C/D (Graph500: 0.57/0.19/0.19/0.05), the source id takes
the row bit and the destination id the column bit of every level, and ids
past |V| fold back by `% |V|`.  Self-loops and duplicate edges are kept.

It draws with `jax.random` (threefry) in one jitted call instead of numpy's
`rng.choice`, so the same seed gives the same graph on the CPU and on the
chip, and a 7.65M-edge graph takes milliseconds of device time instead of
seconds of host time.  It is not bit-identical to the repository's
generator, which draws from numpy's generator.
"""
from __future__ import annotations

import functools
import math

import numpy as np

__all__ = ["edges", "rmat_edges", "seed_key"]


def seed_key(seed: int, stream: int = 0):
    """A threefry key from a seed of any size up to 64 bits, per stream."""
    import jax

    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


@functools.lru_cache(maxsize=None)
def _generator(num_edges: int, levels: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key, cdf, num_nodes):
        def level(i, ids):
            src, dst = ids
            u = jax.random.uniform(jax.random.fold_in(key, i), (num_edges,), jnp.float32)
            q = (u >= cdf[0]).astype(jnp.int32) + (u >= cdf[1]) + (u >= cdf[2])
            bit = jnp.int32(1) << (levels - 1 - i)
            src = src + jnp.where(q >= 2, bit, 0)
            dst = dst + jnp.where(q % 2 == 1, bit, 0)
            return src, dst

        zeros = jnp.zeros(num_edges, jnp.int32)
        src, dst = jax.lax.fori_loop(0, levels, level, (zeros, zeros))
        return src % num_nodes, dst % num_nodes

    return gen


def rmat_edges(
    num_nodes: int, num_edges: int, *, a: float, b: float, c: float, seed: int
) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) as int64 host arrays of `num_edges` R-MAT edges over
    `num_nodes` vertices, drawn from `seed`."""
    import jax.numpy as jnp

    if not (a > 0 and b > 0 and c > 0 and a + b + c < 1):
        raise ValueError(f"R-MAT probabilities a={a} b={b} c={c} leave no room for d")
    if num_nodes >= 1 << 31:
        raise ValueError("vertex ids are int32 on the device")
    levels = int(math.ceil(math.log2(max(2, num_nodes))))
    cdf = jnp.asarray([a, a + b, a + b + c], jnp.float32)
    src, dst = _generator(num_edges, levels)(seed_key(seed), cdf, jnp.int32(num_nodes))
    return np.asarray(src).astype(np.int64), np.asarray(dst).astype(np.int64)


def edges(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The graph of a configuration with `generator: rmat`."""
    return rmat_edges(cfg["num_nodes"], cfg["num_edges"], a=cfg["a"], b=cfg["b"], c=cfg["c"], seed=seed)
