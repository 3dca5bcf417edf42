"""Pallas TPU kernel for one degree-binned ELL bucket of the SpMM hot loop.

This is the paper's CAM-search re-thought for TPU (DESIGN.md §7): the
power-law degree sort that the paper uses for *placement* doubles as the
layout transformation that makes the sparse gather dense-ish.  After
Algorithm 2's sort, rows with similar degree share a bucket of fixed width
W, so the kernel sees a regular (R × W) neighbour grid:

  grid (R, W) — neighbour slot j innermost.  The *scalar-prefetched* column
  ids let the x BlockSpec's index_map name the HBM row group to DMA for
  step (i, j); the accumulator scratch carries partial sums across the W
  steps and the output rows are written once per group.

Blocks are 8 rows tall (the TPU's sublane tile): a step fetches the 8-row
group holding its source row and selects the row with a mask, and 8
consecutive output rows share one (8, D) output block.  Masks rather than
dynamic indices do the selecting, because Mosaic cannot index a tile's
sublane or lane dimension with a runtime scalar.

HBM traffic = 8 × (#valid edges + padding) × D — the ELL fill fraction
(≈0.8 on power-law graphs after the degree sort, measured by
EllBlocks.fill_fraction) and the row-group granularity are the overheads
over the information-theoretic gather floor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ell_spmm_pallas"]

ROWS = 8  # sublane tile height: every block's row count


def _spmm_kernel(cols_ref, x_ref, w_ref, o_ref, acc_ref, *, num_nodes: int, num_rows: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    width = pl.num_programs(1)
    r = i % ROWS

    @pl.when((r == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col = cols_ref[i, j]
    src_rows = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0)
    x_row = jnp.sum(
        jnp.where(src_rows == jnp.minimum(col, num_nodes - 1) % ROWS, x_ref[...], 0.0),
        axis=0,
        keepdims=True,
    ).astype(jnp.float32)  # (1, D)
    w_rows = jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 0)
    w_lanes = jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 1)
    w = jnp.sum(
        jnp.where((w_rows == r) & (w_lanes == j), w_ref[...], 0.0), keepdims=True
    )  # (1, 1)
    w = w * (col < num_nodes).astype(jnp.float32)
    out_rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
    acc_ref[...] += jnp.where(out_rows == r, x_row * w, 0.0)

    @pl.when((j == width - 1) & ((r == ROWS - 1) | (i == num_rows - 1)))
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ell_spmm_pallas(
    x: jnp.ndarray,
    cols: jnp.ndarray,
    wts: jnp.ndarray | None = None,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """x (N, D); cols (R, W) int32 (≥N ⇒ pad); wts (R, W) → (R, D)."""
    n, d = x.shape
    r, w = cols.shape
    if wts is None:
        wts = jnp.ones((r, w), jnp.float32)
    kernel = functools.partial(_spmm_kernel, num_nodes=n, num_rows=r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # cols in SMEM, visible to the x index_map
        grid=(r, w),
        in_specs=[
            pl.BlockSpec(
                (ROWS, d),
                lambda i, j, cols_ref: (jnp.minimum(cols_ref[i, j], n - 1) // ROWS, 0),
            ),
            pl.BlockSpec((ROWS, w), lambda i, j, cols_ref: (i // ROWS, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, d), lambda i, j, cols_ref: (i // ROWS, 0)),
        scratch_shapes=[pltpu.VMEM((ROWS, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret,
    )(cols.astype(jnp.int32), x, wts.astype(jnp.float32))
