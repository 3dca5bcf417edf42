"""Pallas TPU EmbeddingBag: scalar-prefetched row gather + bag reduction.

The TPU adaptation of the paper's CAM lookup: instead of a content search,
the bag indices are *scalar-prefetched into SMEM* so the table BlockSpec's
index_map can name the HBM row group each grid step needs — Pallas then
DMAs only that (8, D) group into VMEM and the kernel selects the row with a
mask.  No full-table gather ever materialises; HBM traffic is
`8 × Σ bag lengths × D`: 8 rows is the TPU's sublane tile, the least a
block of a (vocab, D) table may be, and masks rather than dynamic indices
do the selecting because Mosaic cannot index a tile's sublane or lane
dimension with a runtime scalar.

Grid (B, T, L): the bag dimension is innermost so the accumulator scratch
carries across the L steps of one (b, t) bag; the (T, D) output block of one
batch row stays resident across its T·L steps and is written on the last.

Production note: SMEM is ~1 MB/core and the prefetched ids pad to (8, 128)
tiles there, so real deployments tile B into grid-sized chunks before the
call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["embedding_bag_pallas"]

ROWS = 8  # sublane tile height: rows per table block


def _bag_kernel(ids_ref, table_ref, w_ref, o_ref, acc_ref, *, vocab: int):
    b = pl.program_id(0)
    t = pl.program_id(1)
    l = pl.program_id(2)
    num_tables = pl.num_programs(1)
    bag_len = pl.num_programs(2)

    @pl.when((t == 0) & (l == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = ids_ref[b, t, l]
    valid = (idx >= 0) & (idx < vocab)
    rows = table_ref[0]  # (ROWS, D)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    row = jnp.sum(
        jnp.where(row_ids == jnp.clip(idx, 0, vocab - 1) % ROWS, rows, 0.0),
        axis=0,
        keepdims=True,
    ).astype(jnp.float32)  # (1, D)
    wts = w_ref[0]  # (T, L)
    w_t = jax.lax.broadcasted_iota(jnp.int32, wts.shape, 0)
    w_l = jax.lax.broadcasted_iota(jnp.int32, wts.shape, 1)
    w = jnp.sum(jnp.where((w_t == t) & (w_l == l), wts, 0.0), keepdims=True)  # (1, 1)
    w = w * valid.astype(jnp.float32)
    out_t = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
    acc_ref[...] += jnp.where(out_t == t, row * w, 0.0)

    @pl.when((t == num_tables - 1) & (l == bag_len - 1))
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_pallas(
    tables: jnp.ndarray,
    ids: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """tables (T, V, D); ids (B, T, L); weights (B, T, L) → (B, T, D)."""
    t, v, d = tables.shape
    b, t2, l = ids.shape
    assert t == t2
    if weights is None:
        weights = jnp.ones((b, t, l), jnp.float32)
    kernel = functools.partial(_bag_kernel, vocab=v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # ids live in SMEM, visible to index_maps
        grid=(b, t, l),
        in_specs=[
            # row group chosen by the prefetched id — the indexed-DMA gather
            pl.BlockSpec(
                (1, ROWS, d),
                lambda b_, t_, l_, ids_ref: (
                    t_, jnp.clip(ids_ref[b_, t_, l_], 0, v - 1) // ROWS, 0
                ),
            ),
            pl.BlockSpec((1, t, l), lambda b_, t_, l_, ids_ref: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, d), lambda b_, t_, l_, ids_ref: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, d), tables.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), tables, weights.astype(jnp.float32))
