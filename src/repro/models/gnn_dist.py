"""Distributed GNN forward via halo exchange (§Perf — the paper's technique
as the optimization).

`gin_forward_halo` is `gnn.gin_forward` re-expressed per-engine under
shard_map: node features live as (P, n_local, d) sharded on the flat device
axis, each layer does one halo exchange (all_to_all of the partition's cut)
and a purely local gather + segment_sum + MLP.  Numerically identical to
the global formulation (tests/test_multidevice_subprocess.py).

The same plan/primitive generalises to GAT (halo the Wh rows; edge softmax
is dst-local under destination-cut), PNA (halo once per layer, all four
aggregators local) and GraphCast (one plan per bipartite edge set) — GIN is
wired first because gin-tu × ogb_products is the worst collective/compute
cell of the sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.graph.halo import HaloPlan, halo_extend
from repro.models.gnn import GnnConfig, _mlp_apply

__all__ = ["gin_halo_loss_fn", "gin_forward_halo", "batch_specs_halo"]

AXIS = "engines"  # default flat device axis (tests); production passes the
# mesh's full axis-name tuple so the flat engine grid spans the whole pod.


def _gin_local_steps(params, cfg, axis_name, x_l, send_idx, src_slot, dst_slot, node_ok):
    """Per-engine body: x_l (n_local, d_in) → logits (n_local, d_out)."""
    n_local = x_l.shape[0]
    h = x_l
    for lp in params["layers"]:
        ext = halo_extend(h, send_idx, axis_name)  # (n_local + P·h_pair, d)
        extz = jnp.concatenate([ext, jnp.zeros((1, ext.shape[1]), ext.dtype)])
        msg = extz[src_slot]  # (e_local, d); padded edges hit the zero row
        agg = jax.ops.segment_sum(msg, dst_slot, num_segments=n_local + 1)[:n_local]
        eps = lp["eps"] if cfg.gin_eps_learnable else 0.0
        h = _mlp_apply(lp["mlp"], (1.0 + eps) * h + agg)
        h = jax.nn.silu(h)
    logits = jnp.einsum("nd,dc->nc", h, params["head"]["w"].astype(h.dtype))
    return logits + params["head"]["b"].astype(h.dtype)


def gin_forward_halo(params, batch, cfg: GnnConfig, mesh):
    """batch arrays carry the plan layout (leading P axis, see
    batch_specs_halo); returns (P, n_local, d_out) logits."""
    axis = tuple(mesh.axis_names)
    axis = axis[0] if len(axis) == 1 else axis
    body = functools.partial(_gin_local_steps, params, cfg, axis)

    def local(x, send_idx, src_slot, dst_slot, node_ok):
        return body(x[0], send_idx[0], src_slot[0], dst_slot[0], node_ok[0])[None]

    sharded = P(axis)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded, sharded),
        out_specs=sharded,
        check_vma=False,
    )(batch["x"], batch["send_idx"], batch["src_slot"], batch["dst_slot"],
      batch["node_mask"])


def gin_halo_loss_fn(params, batch, cfg: GnnConfig, mesh):
    logits = gin_forward_halo(params, batch, cfg, mesh).astype(jnp.float32)
    labels = batch["labels"]
    mask = (batch["train_mask"] & batch["node_mask"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32), -1)[..., 0]
    return jnp.sum((logz - gold) * mask) / jnp.maximum(mask.sum(), 1.0)


def batch_specs_halo(sizes: dict, d_feat: int, n_classes: int):
    """ShapeDtypeStructs for the plan-layout batch (P-leading arrays)."""
    Pn, n_l, e_l, h = (sizes["num_devices"], sizes["n_local"],
                       sizes["e_local"], sizes["h_pair"])
    f32, i32, b_ = jnp.float32, jnp.int32, jnp.bool_
    return {
        "x": jax.ShapeDtypeStruct((Pn, n_l, d_feat), f32),
        "send_idx": jax.ShapeDtypeStruct((Pn, Pn, h), i32),
        "src_slot": jax.ShapeDtypeStruct((Pn, e_l), i32),
        "dst_slot": jax.ShapeDtypeStruct((Pn, e_l), i32),
        "node_mask": jax.ShapeDtypeStruct((Pn, n_l), b_),
        "labels": jax.ShapeDtypeStruct((Pn, n_l), i32),
        "train_mask": jax.ShapeDtypeStruct((Pn, n_l), b_),
    }


def pack_batch(plan: HaloPlan, x, labels, train_mask):
    """Host-side: vertex-ordered arrays → plan layout (for real training)."""
    Pn, n_l = plan.num_devices, plan.n_local
    s2v = plan.slot_to_vertex
    ok = s2v >= 0
    d = x.shape[1]
    xb = np.zeros((Pn, n_l, d), np.float32)
    lb = np.zeros((Pn, n_l), np.int32)
    tm = np.zeros((Pn, n_l), bool)
    xb[ok] = x[s2v[ok]]
    lb[ok] = labels[s2v[ok]]
    tm[ok] = train_mask[s2v[ok]]
    return {
        "x": xb, "send_idx": plan.send_idx, "src_slot": plan.src_slot,
        "dst_slot": plan.dst_slot, "node_mask": ok, "labels": lb,
        "train_mask": tm,
    }
