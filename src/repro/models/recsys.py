"""DCN-v2 recommender: sparse embedding tables → cross network → MLP tower.

JAX has no native EmbeddingBag — the lookup is `jnp.take` +
`jax.ops.segment_sum` (multi-hot) routed through
`repro.kernels.embedding_bag` (Pallas on TPU, jnp oracle elsewhere).

Paper tie-in (DESIGN.md §4): embedding-row access frequency is power-law
(hot items ≡ hub vertices).  Tables shard row-wise over the "model" axis by
the same degree-sorted cyclic partition (Algorithm 2), and the hot-row
replication plan (repro.core.replication) turns the hottest rows' gathers
into broadcast-local reads — the hub-replication extension applied to
embedding traffic.

Shapes (assignment): n_dense=13, n_sparse=26, embed_dim=16,
n_cross_layers=3, mlp 1024-1024-512, cross interaction.  `retrieval_scores`
scores one query against ~1M candidates as a sharded matvec (no loop).
"""
from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import Initializer
from repro.models import sharding as sharding_lib
from repro.models.sharding import MeshRules, axis_if_divisible, constrain

__all__ = ["DcnConfig", "init_params", "param_specs", "forward", "loss_fn",
           "retrieval_scores", "user_tower"]

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DcnConfig:
    name: str = "dcn-v2"
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 16
    rows_per_table: int = 1_000_000
    multi_hot: int = 1  # ids per sparse feature (1 ⇒ plain gather)
    lookup_impl: str = "gather"  # "gather" | "psum_model" (§Perf iteration)
    n_cross_layers: int = 3
    mlp_dims: tuple[int, ...] = (1024, 1024, 512)
    cross_rank: int = 0  # 0 ⇒ full-rank W (DCN-v2 full); >0 ⇒ low-rank UV
    dtype: typing.Any = jnp.float32
    param_dtype: typing.Any = jnp.float32
    hot_rows_replicated: int = 0  # top-K hot rows replicated (hub replication)
    rules: MeshRules = dataclasses.field(default_factory=MeshRules)

    @property
    def d_input(self) -> int:
        return self.n_dense + self.n_sparse * self.embed_dim

    @property
    def num_params(self) -> int:
        d0 = self.d_input
        cross = self.n_cross_layers * (
            d0 * d0 + 2 * d0 if self.cross_rank == 0 else 2 * d0 * self.cross_rank + 2 * d0
        )
        dims = [d0, *self.mlp_dims]
        mlp = sum(a * b + b for a, b in zip(dims[:-1], dims[1:])) + self.mlp_dims[-1] + 1
        emb = self.n_sparse * self.rows_per_table * self.embed_dim
        return emb + cross + mlp


def init_params(cfg: DcnConfig, key: jax.Array) -> dict:
    ini = Initializer(key)
    d0 = cfg.d_input
    params: dict = {
        # one stacked table (T, V, D): uniform vocab keeps sharding clean
        "tables": ini.normal(
            (cfg.n_sparse, cfg.rows_per_table, cfg.embed_dim), 0.01, cfg.param_dtype
        ),
    }
    cross = []
    for _ in range(cfg.n_cross_layers):
        if cfg.cross_rank == 0:
            cross.append({"w": ini.fan_in((d0, d0), cfg.param_dtype), "b": ini.zeros((d0,))})
        else:
            cross.append(
                {
                    "u": ini.fan_in((d0, cfg.cross_rank), cfg.param_dtype),
                    "v": ini.fan_in((cfg.cross_rank, d0), cfg.param_dtype),
                    "b": ini.zeros((d0,)),
                }
            )
    params["cross"] = cross
    mlp = []
    dims = [d0, *cfg.mlp_dims]
    for a, b in zip(dims[:-1], dims[1:]):
        mlp.append({"w": ini.fan_in((a, b), cfg.param_dtype), "b": ini.zeros((b,))})
    params["mlp"] = mlp
    params["out"] = {"w": ini.fan_in((cfg.mlp_dims[-1], 1), cfg.param_dtype), "b": ini.zeros((1,))}
    return params


def param_specs(cfg: DcnConfig, mesh=None) -> dict:
    from jax.sharding import PartitionSpec as P

    r = cfg.rules
    row_ax = axis_if_divisible(cfg.rows_per_table, r.model, mesh)
    d0 = cfg.d_input
    specs: dict = {"tables": P(None, row_ax, None)}  # row-sharded tables
    specs["cross"] = [
        {"w": P(None, None), "b": P(None)}
        if cfg.cross_rank == 0
        else {"u": P(None, None), "v": P(None, None), "b": P(None)}
        for _ in range(cfg.n_cross_layers)
    ]
    dims = [d0, *cfg.mlp_dims]
    specs["mlp"] = [
        {"w": P(axis_if_divisible(a, r.fsdp, mesh), axis_if_divisible(b, r.model, mesh)),
         "b": P(axis_if_divisible(b, r.model, mesh))}
        for a, b in zip(dims[:-1], dims[1:])
    ]
    specs["out"] = {"w": P(None, None), "b": P(None)}
    return specs


# ------------------------------ lookup -------------------------------------


def embedding_lookup(cfg: DcnConfig, tables: Array, ids: Array, weights: Array | None = None) -> Array:
    """ids: (B, T) single-hot or (B, T, L) multi-hot → (B, T·D) bag features."""
    from repro.kernels.embedding_bag.ops import embedding_bag

    b = ids.shape[0]
    if ids.ndim == 2:  # single-hot = bag of length 1
        ids = ids[..., None]
        weights = None if weights is None else weights[..., None]
    if cfg.lookup_impl == "psum_model":
        emb = _lookup_psum_model(cfg, tables, ids, weights)
    else:
        emb = embedding_bag(tables, ids, weights)  # (B, T, D)
    return emb.reshape(b, cfg.n_sparse * cfg.embed_dim)


def _lookup_psum_model(cfg: DcnConfig, tables: Array, ids: Array,
                       weights: Array | None) -> Array:
    """§Perf: sharded lookup as masked-local-gather + psum over "model".

    Tables are row-sharded on "model"; each shard gathers only the rows it
    owns (out-of-range ids masked to zero) and a psum over the model axis
    assembles the bags — 14 MB of collective per step instead of GSPMD's
    dense-gradient all-reduce of the whole table (3.4 GB): the backward of
    the masked gather is a *local* scatter-add, and the transpose of psum is
    a broadcast, so the table gradient never crosses the model axis.
    (Hot rows ≡ hubs: because Algorithm 2's cyclic deal spreads hot rows
    across shards, per-shard gather work stays balanced — load_balance
    measured in tests.)"""
    mesh = sharding_lib.active_mesh()
    if mesh is None or "model" not in (mesh.shape or {}):
        from repro.kernels.embedding_bag.ops import embedding_bag

        return embedding_bag(tables, ids, weights)
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape["model"]
    t, v, d = cfg.n_sparse, cfg.rows_per_table, cfg.embed_dim
    assert v % ep == 0, "rows_per_table must divide the model axis"
    v_l = v // ep
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    b = ids.shape[0]
    dp_ok = b % int(np.prod([mesh.shape[a] for a in dp_axes])) == 0
    ids_spec = P(dp_axes if dp_ok else None, None, None)
    w = weights if weights is not None else jnp.ones(ids.shape, tables.dtype)

    def body(tab_l, ids_l, w_l):
        lo = jax.lax.axis_index("model") * v_l
        loc = ids_l - lo
        ok = (loc >= 0) & (loc < v_l)
        safe = jnp.clip(loc, 0, v_l - 1)
        rows = tab_l[jnp.arange(t)[None, :, None], safe]  # (B_l, T, L, D)
        ww = ok.astype(tab_l.dtype) * w_l.astype(tab_l.dtype)
        return jax.lax.psum((rows * ww[..., None]).sum(2), "model")

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, "model", None), ids_spec, ids_spec),
        out_specs=P(dp_axes if dp_ok else None, None, None),
        check_vma=False,
    )(tables, ids, w)


# ------------------------------ forward ------------------------------------


def _cross_layer(lp: dict, x0: Array, x: Array) -> Array:
    if "w" in lp:
        xw = jnp.einsum("bd,de->be", x, lp["w"].astype(x.dtype))
    else:
        xw = jnp.einsum("br,rd->bd", jnp.einsum("bd,dr->br", x, lp["u"].astype(x.dtype)),
                        lp["v"].astype(x.dtype))
    return x0 * (xw + lp["b"].astype(x.dtype)) + x


def forward(params: dict, batch: dict, cfg: DcnConfig) -> Array:
    """batch: dense (B, n_dense) fp32, sparse_ids (B, T[, L]) int32
    → logits (B,)."""
    r = cfg.rules
    dense = batch["dense"].astype(cfg.dtype)
    emb = embedding_lookup(cfg, params["tables"], batch["sparse_ids"],
                           batch.get("sparse_weights"))
    x0 = jnp.concatenate([dense, emb.astype(cfg.dtype)], axis=-1)
    x0 = r.act_tokens(x0)
    x = x0
    for lp in params["cross"]:
        x = _cross_layer(lp, x0, x)
    h = x
    for lp in params["mlp"]:
        h = jax.nn.relu(jnp.einsum("bd,df->bf", h, lp["w"].astype(h.dtype)) + lp["b"].astype(h.dtype))
        h = r.act_tokens(h)
    logit = jnp.einsum("bd,do->bo", h, params["out"]["w"].astype(h.dtype)) + params["out"][
        "b"
    ].astype(h.dtype)
    return logit[:, 0]


def loss_fn(params: dict, batch: dict, cfg: DcnConfig) -> Array:
    logits = forward(params, batch, cfg).astype(jnp.float32)
    labels = batch["labels"].astype(jnp.float32)
    # numerically-stable BCE-with-logits
    return jnp.mean(jnp.maximum(logits, 0.0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ----------------------------- retrieval -----------------------------------


def user_tower(params: dict, batch: dict, cfg: DcnConfig) -> Array:
    """Query embedding = the MLP tower's last hidden layer (B, mlp[-1])."""
    r = cfg.rules
    dense = batch["dense"].astype(cfg.dtype)
    emb = embedding_lookup(cfg, params["tables"], batch["sparse_ids"])
    x0 = jnp.concatenate([dense, emb.astype(cfg.dtype)], axis=-1)
    x = x0
    for lp in params["cross"]:
        x = _cross_layer(lp, x0, x)
    h = x
    for lp in params["mlp"]:
        h = jax.nn.relu(jnp.einsum("bd,df->bf", h, lp["w"].astype(h.dtype)) + lp["b"].astype(h.dtype))
    return h


def retrieval_scores(
    params: dict, batch: dict, candidates: Array, cfg: DcnConfig, *, top_k: int = 100
) -> tuple[Array, Array]:
    """Score `batch` queries against (N_cand, d) candidates (sharded over all
    mesh axes on the candidate dim) — one batched matvec, then global top-k."""
    r = cfg.rules
    cand = constrain(candidates, (*r.batch, r.model), None)
    u = user_tower(params, batch, cfg)  # (B, d)
    scores = jnp.einsum("nd,bd->bn", cand.astype(u.dtype), u)  # (B, N_cand)
    vals, idx = jax.lax.top_k(scores.astype(jnp.float32), top_k)
    return vals, idx
