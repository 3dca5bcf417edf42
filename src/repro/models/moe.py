"""Mixture-of-Experts FFN: shared + routed experts, top-k, capacity dispatch.

Two execution paths, selected by `MoEConfig.impl`:

  * "local"       — sort-based capacity dispatch expressed as one global
    program (argsort + scatter).  Correct everywhere (single-device smoke
    tests, no-mesh CPU runs); under pjit the sort is global and the experts
    replicate when num_experts doesn't divide the model axis.
  * "ep_shardmap" — production expert parallelism: experts sharded over the
    "model" mesh axis, tokens exchanged with `lax.all_to_all` inside
    `shard_map`.  This is the path the multi-pod dry-run lowers, and the one
    whose all-to-all bytes the roofline's collective term measures.

Paper tie-in (DESIGN.md §4): expert→device placement is the same assignment
problem as the paper's Algorithm 4 — routed-token counts are power-law
skewed across experts (hot experts ≡ hub vertices), so
`expert_device_permutation` reuses `repro.core.placement` to pick which
expert block lands on which model-axis position, minimising hop-weighted
all-to-all traffic on the ICI ring.
"""
from __future__ import annotations

import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import (
    MeshRules,
    active_mesh,
    axis_if_divisible,
    constrain,
)

__all__ = [
    "MoEConfig",
    "layer_shapes",
    "layer_specs",
    "moe_block",
    "load_balance_loss",
    "expert_device_permutation",
]

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    d_ff_shared: int = 0  # 0 ⇒ no shared expert (olmoe); >0 ⇒ qwen2-moe style
    capacity_factor: float = 1.25
    norm_topk: bool = True  # olmoe normalises top-k probs; qwen2-moe does not
    impl: str = "local"  # "local" | "ep_shardmap"
    ep_axis: str = "model"
    aux_loss_weight: float = 0.01
    router_z_weight: float = 1e-3

    def padded_experts(self, ep_size: int) -> int:
        """Experts padded up to a multiple of the EP axis (60 → 64 on 16)."""
        return -(-self.num_experts // ep_size) * ep_size


def layer_shapes(m: MoEConfig, d_model: int) -> dict[str, tuple[int, ...]]:
    shapes = {
        "router": (d_model, m.num_experts),
        "we_gate": (m.num_experts, d_model, m.d_ff_expert),
        "we_up": (m.num_experts, d_model, m.d_ff_expert),
        "we_down": (m.num_experts, m.d_ff_expert, d_model),
    }
    if m.d_ff_shared:
        shapes.update(
            {
                "ws_gate": (d_model, m.d_ff_shared),
                "ws_up": (d_model, m.d_ff_shared),
                "ws_down": (m.d_ff_shared, d_model),
                "ws_sig": (d_model, 1),  # qwen2-moe shared-expert sigmoid gate
            }
        )
    return shapes


def layer_specs(m: MoEConfig, d_model: int, r: MeshRules, *, prefix: int = 0, mesh=None) -> dict:
    """Expert stacks shard E on model when divisible, else fall back to
    sharding the expert FFN dim on model (qwen's 60 experts on a 16-way axis)."""
    from jax.sharding import PartitionSpec as P

    e_ax = axis_if_divisible(m.num_experts, r.model, mesh)
    f_ax = None if e_ax is not None else axis_if_divisible(m.d_ff_expert, r.model, mesh)
    pre = [None] * prefix
    specs = {
        "router": P(*pre, axis_if_divisible(d_model, r.fsdp, mesh), None),
        "we_gate": P(*pre, e_ax, axis_if_divisible(d_model, r.fsdp, mesh), f_ax),
        "we_up": P(*pre, e_ax, axis_if_divisible(d_model, r.fsdp, mesh), f_ax),
        "we_down": P(*pre, e_ax, f_ax, axis_if_divisible(d_model, r.fsdp, mesh)),
    }
    if m.d_ff_shared:
        specs.update(
            {
                "ws_gate": r.col_parallel(d_model, m.d_ff_shared, prefix=prefix, mesh=mesh),
                "ws_up": r.col_parallel(d_model, m.d_ff_shared, prefix=prefix, mesh=mesh),
                "ws_down": r.row_parallel(m.d_ff_shared, d_model, prefix=prefix, mesh=mesh),
                "ws_sig": P(*pre, None, None),
            }
        )
    return specs


# ------------------------------ routing -----------------------------------


def _router(m: MoEConfig, lp: dict, x: Array) -> tuple[Array, Array, Array]:
    """x (N, D) → (topk_probs (N,k), topk_idx (N,k), full probs (N,E))."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), lp["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p.astype(x.dtype), top_i, probs


def load_balance_loss(probs: Array, top_idx: Array, num_experts: int) -> Array:
    """Switch-style aux loss: E · Σ_e f_e·p̄_e (1.0 at perfect balance)."""
    k = top_idx.shape[-1]
    assign = jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32).sum(-2)  # (N, E)
    f = assign.mean(0) / k
    p = probs.mean(0)
    return num_experts * jnp.sum(f * p)


def _expert_ffn(we_gate: Array, we_up: Array, we_down: Array, buf: Array) -> Array:
    """buf (E, C, D) → (E, C, D) through per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, we_gate.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, we_up.astype(buf.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, we_down.astype(buf.dtype))


def _sort_dispatch(e_flat: Array, num_segments: int) -> tuple[Array, Array]:
    """Stable-sort slots by expert id; return (order, position-within-expert)."""
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    counts = jax.ops.segment_sum(jnp.ones_like(e_sorted), e_sorted, num_segments=num_segments)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(e_sorted.shape[0]) - starts[e_sorted]
    return order, pos


# --------------------------- local (global-program) path -------------------


def _moe_local(m: MoEConfig, lp: dict, x: Array, r: MeshRules) -> Array:
    """Sort-based capacity dispatch as one global program.  x: (N, D)."""
    n, d = x.shape
    top_p, top_i, _ = _router(m, lp, x)
    k, E = m.top_k, m.num_experts
    C = max(8, int(np.ceil(n * k / E * m.capacity_factor)))
    e_flat = top_i.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(n), k)
    g_flat = top_p.reshape(-1)
    order, pos = _sort_dispatch(e_flat, E)
    e_s, t_s, g_s = e_flat[order], t_flat[order], g_flat[order]
    keep = pos < C
    dest = jnp.where(keep, e_s * C + pos, E * C)
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[dest].set(x[t_s])
    buf = r.act_ecd(buf[: E * C].reshape(E, C, d))
    y = r.act_ecd(_expert_ffn(lp["we_gate"], lp["we_up"], lp["we_down"], buf))
    y_slot = y.reshape(E * C, d)[jnp.minimum(dest, E * C - 1)]
    y_slot = y_slot * (keep & (dest < E * C))[:, None] * g_s[:, None]
    return jnp.zeros((n, d), x.dtype).at[t_s].add(y_slot)


# --------------------------- expert-parallel shard_map path ----------------


def _moe_ep_local_body(m: MoEConfig, ep: int, e_pad: int, x, router_w, wg, wu, wd):
    """Per-device body under shard_map.  x: (N_l, D) local tokens;
    wg/wu/wd: (E_l, D, F) local expert slab.  Two-stage dispatch:
    (1) all_to_all tokens to the device owning their expert,
    (2) local grouping by expert, FFN, and the reverse path.
    """
    axis = m.ep_axis
    n_l, d = x.shape
    e_l = e_pad // ep
    k = m.top_k
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if m.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    top_p = top_p.astype(x.dtype)

    # --- stage 1: route slots to destination devices ---
    dev_of = top_i.reshape(-1) // e_l  # (N_l·k,)
    loc_e = top_i.reshape(-1) % e_l
    t_flat = jnp.repeat(jnp.arange(n_l), k)
    g_flat = top_p.reshape(-1)
    Cs = max(8, int(np.ceil(n_l * k / ep * m.capacity_factor)))
    order, pos = _sort_dispatch(dev_of, ep)
    keep = pos < Cs
    slot = jnp.where(keep, dev_of[order] * Cs + pos, ep * Cs)
    send_x = jnp.zeros((ep * Cs + 1, d), x.dtype).at[slot].set(x[t_flat[order]])[:-1]
    send_e = jnp.full((ep * Cs + 1,), e_l, jnp.int32).at[slot].set(loc_e[order].astype(jnp.int32))[:-1]
    send_g = jnp.zeros((ep * Cs + 1,), x.dtype).at[slot].set(g_flat[order])[:-1]
    recv_x = jax.lax.all_to_all(send_x.reshape(ep, Cs, d), axis, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e.reshape(ep, Cs), axis, 0, 0, tiled=False)
    recv_g = jax.lax.all_to_all(send_g.reshape(ep, Cs), axis, 0, 0, tiled=False)
    recv_x = recv_x.reshape(ep * Cs, d)
    recv_e = recv_e.reshape(ep * Cs)  # local expert id, e_l = invalid slot
    recv_g = recv_g.reshape(ep * Cs)

    # --- stage 2: group received tokens by local expert ---
    Ce = max(8, int(np.ceil(ep * Cs / max(e_l, 1) * m.capacity_factor)))
    order2, pos2 = _sort_dispatch(recv_e, e_l + 1)
    e2 = recv_e[order2]
    keep2 = (pos2 < Ce) & (e2 < e_l)
    dest2 = jnp.where(keep2, e2 * Ce + pos2, e_l * Ce)
    buf = jnp.zeros((e_l * Ce + 1, d), x.dtype).at[dest2].set(recv_x[order2])[:-1]
    y = _expert_ffn(wg, wu, wd, buf.reshape(e_l, Ce, d)).reshape(e_l * Ce, d)
    # reverse stage 2: back to received-slot order
    y_recv = jnp.zeros((ep * Cs, d), x.dtype)
    y_recv = y_recv.at[order2].set(y[jnp.minimum(dest2, e_l * Ce - 1)] * keep2[:, None])
    # reverse stage 1: all_to_all back and combine
    y_send = jax.lax.all_to_all(y_recv.reshape(ep, Cs, d), axis, 0, 0, tiled=False)
    y_slot = y_send.reshape(ep * Cs, d) * send_g[:, None]  # gate at the source
    out = jnp.zeros((n_l, d), x.dtype)
    tok_sorted = t_flat[order]
    contrib = y_slot[jnp.minimum(slot, ep * Cs - 1)] * (slot < ep * Cs)[:, None]
    return out.at[tok_sorted].add(contrib)


def _moe_ep(m: MoEConfig, lp: dict, x: Array, r: MeshRules) -> Array:
    """shard_map expert parallelism.  x: (N, D) sharded on the DP axes."""
    from jax.sharding import PartitionSpec as P

    mesh = active_mesh()
    if mesh is None or m.ep_axis not in (mesh.shape or {}):
        return _moe_local(m, lp, x, r)
    ep = mesh.shape[m.ep_axis]
    e_pad = m.padded_experts(ep)
    pad = e_pad - m.num_experts

    def pad_e(w):
        return jnp.pad(w, ((0, pad), (0, 0), (0, 0))) if pad else w

    wg, wu, wd = pad_e(lp["we_gate"]), pad_e(lp["we_up"]), pad_e(lp["we_down"])
    # Tokens shard over every mesh axis (DP axes × the EP axis — the EP split
    # is Megatron-SP sequence sharding folded into the token dim), so each
    # device routes a disjoint token slice and all_to_all moves tokens
    # between expert owners within each data row.
    dp_axes = tuple(a for a in mesh.axis_names if a != m.ep_axis)
    tok_spec = P((*dp_axes, m.ep_axis), None)
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    n_tok = x.shape[0]
    n_tok_pad = -(-n_tok // n_dev) * n_dev  # decode batches can be < n_dev
    if n_tok_pad != n_tok:
        x = jnp.pad(x, ((0, n_tok_pad - n_tok), (0, 0)))
    body = functools.partial(_moe_ep_local_body, m, ep, e_pad)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            tok_spec,
            P(None, None),  # router weights replicated
            P(m.ep_axis, None, None),
            P(m.ep_axis, None, None),
            P(m.ep_axis, None, None),
        ),
        out_specs=tok_spec,
        check_vma=False,
    )(x, lp["router"], wg, wu, wd)
    return out[:n_tok] if n_tok_pad != n_tok else out


# ------------------------------ public block -------------------------------


def moe_block(m: MoEConfig, lp: dict, x: Array, *, rules: MeshRules | None = None) -> Array:
    """x: (B, S, D) → (B, S, D).  Routed experts (+ optional shared expert)."""
    r = rules or MeshRules()
    b, s, d = x.shape
    flat = r.act_tokens_sp(x.reshape(b * s, d))
    if m.impl == "ep_shardmap":
        routed = _moe_ep(m, lp, flat, r)
    else:
        routed = _moe_local(m, lp, flat, r)
    out = r.act_btd(routed.reshape(b, s, d))
    if m.d_ff_shared:
        g = jnp.einsum("bsd,df->bsf", x, lp["ws_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, lp["ws_up"].astype(x.dtype))
        shared = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, lp["ws_down"].astype(x.dtype))
        gate = jax.nn.sigmoid(jnp.einsum("bsd,dz->bsz", x, lp["ws_sig"].astype(x.dtype)))
        out = out + shared * gate
    return out


# ---------------------- paper tie-in: expert placement ---------------------


def expert_device_permutation(
    route_counts: np.ndarray,
    ep_size: int,
    *,
    topology=None,
    seed: int = 0,
) -> tuple[np.ndarray, dict[str, float]]:
    """Choose which expert block lands on which model-axis position.

    route_counts: (num_dp_shards, num_experts) token counts from routing
    statistics.  Experts are grouped into `ep_size` blocks (the sharding
    unit); block-to-block traffic is the all-to-all volume between the DP
    shard co-resident with block i and the experts in block j.  Minimising
    hop-weighted volume on the ICI ring is exactly the paper's Algorithm 4
    with merged nodes — solved with the same greedy+2opt machinery.

    Returns (perm, stats): perm[b] = device position for expert block b.
    Hot experts are first spread across blocks (degree-sorted cyclic deal —
    Algorithm 2 step 1-2 applied to expert "degree" = routed token count).
    """
    from repro.core.noc import Torus2D
    from repro.core import placement as placement_lib

    counts = np.asarray(route_counts, dtype=np.float64)
    n_dp, n_exp = counts.shape
    # Algorithm 2 on experts: sort by load desc, deal cyclically into blocks.
    order = np.argsort(-counts.sum(0), kind="stable")
    block_of = np.empty(n_exp, dtype=np.int64)
    block_of[order] = np.arange(n_exp) % ep_size
    # block traffic: DP shard d (co-located with block d % ep) → expert block b
    traffic = np.zeros((ep_size, ep_size))
    for d in range(n_dp):
        src_block = d % ep_size
        for b in range(ep_size):
            traffic[src_block, b] += counts[d, block_of == b].sum()
    np.fill_diagonal(traffic, 0.0)
    if topology is None:
        kx = int(np.sqrt(ep_size))
        while ep_size % kx:
            kx -= 1
        topology = Torus2D(kx, ep_size // kx)
    greedy = placement_lib.greedy_placement(traffic, topology, seed=seed)
    # Steepest-descent refinement (same kernel as DeviceMapper): deterministic
    # full 2-opt local optimum instead of 4000 random probes.
    placed = placement_lib.two_opt_best_move(greedy, traffic)
    identity = placement_lib.Placement(topology, np.arange(ep_size), "identity")
    h_opt, h_id = placed.average_hops(traffic), identity.average_hops(traffic)
    if h_opt >= h_id:
        placed, h_opt = identity, h_id
    stats = {
        "hops_optimized": float(h_opt),
        "hops_identity": float(h_id),
        "hop_reduction": float(h_id / h_opt) if h_opt else 1.0,
        "load_balance": float(
            np.bincount(block_of, weights=counts.sum(0), minlength=ep_size).max()
            / max(counts.sum() / ep_size, 1e-9)
        ),
    }
    return placed.site.copy(), stats
