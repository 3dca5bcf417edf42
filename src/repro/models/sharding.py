"""Sharding rules: one place that decides how every tensor lands on the mesh.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
  * batch      → ("pod", "data")          (DP across pods and the data axis)
  * params     → FSDP on "data" for one non-model dim + TP on "model"
                 (Megatron column/row parallel; vocab sharded on "model")
  * residuals  → batch on DP axes + sequence on "model" (Megatron-SP)
  * experts    → "model" (expert parallelism, see repro.models.moe)

Non-divisible dims fall back to replication (`axis_if_divisible`) instead of
relying on GSPMD padding, so the roofline's useful-FLOPs ratio stays honest.
`constrain` is a no-op outside a mesh context, which keeps single-device
smoke tests free of sharding machinery.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["MeshRules", "constrain", "axis_if_divisible", "active_mesh"]


def axis_if_divisible(dim: int, axis: str | tuple[str, ...] | None, mesh=None):
    """Return `axis` if `dim` divides evenly over it on the active mesh."""
    if axis is None:
        return None
    mesh = mesh or active_mesh()
    if mesh is None:
        return axis
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    size = 1
    for a in axes:
        if a not in mesh.shape:
            return None
        size *= mesh.shape[a]
    return axis if dim % size == 0 else None


def active_mesh():
    """The ambient mesh set by `jax.set_mesh`, or None outside any mesh."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.shape else None


def constrain(x, *spec):
    """with_sharding_constraint that degrades to identity with no mesh."""
    mesh = active_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    # drop axis names the mesh doesn't have (e.g. "pod" on single-pod)
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, str):
            clean.append(s if s in mesh.axis_names else None)
        else:
            kept = tuple(a for a in s if a in mesh.axis_names)
            clean.append(kept if kept else None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*clean))
    except Exception:
        return x


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Canonical axis assignments; `multi_pod` only adds "pod" to batch.

    strategy:
      "tp_sp" — Megatron tensor parallel on "model" + sequence parallelism
                (the memory-safe default for wide models and the EP home
                for MoE experts).
      "fsdp"  — ZeRO-3: parameters sharded over the flattened
                ("data","model") axes, batch over everything, no TP
                collectives.  §Perf iteration 1 showed this beats tp_sp by
                >20× on collective bytes for ≤34B dense training, where
                per-layer weight gathers ≪ sequence gathers.
    """

    multi_pod: bool = False
    strategy: str = "tp_sp"

    @property
    def batch(self) -> tuple[str, ...]:
        if self.strategy == "fsdp":
            return ("pod", "data", "model") if self.multi_pod else ("data", "model")
        return ("pod", "data") if self.multi_pod else ("data",)

    @property
    def fsdp(self):
        return ("data", "model") if self.strategy == "fsdp" else "data"

    @property
    def model(self):
        return None if self.strategy == "fsdp" else "model"

    # --- parameter specs (leading `prefix_dims` e.g. stacked layer dim) ---
    def col_parallel(self, d_in: int, d_out: int, *, prefix: int = 0, mesh=None) -> P:
        """y = x @ W, W (d_in, d_out): shard d_out on model, d_in FSDP."""
        return P(
            *([None] * prefix),
            axis_if_divisible(d_in, self.fsdp, mesh),
            axis_if_divisible(d_out, self.model, mesh),
        )

    def row_parallel(self, d_in: int, d_out: int, *, prefix: int = 0, mesh=None) -> P:
        """W (d_in, d_out): shard d_in on model (contracted), d_out FSDP."""
        return P(
            *([None] * prefix),
            axis_if_divisible(d_in, self.model, mesh),
            axis_if_divisible(d_out, self.fsdp, mesh),
        )

    def vocab_embed(self, vocab: int, d_model: int, *, mesh=None) -> P:
        return P(
            axis_if_divisible(vocab, self.model, mesh),
            axis_if_divisible(d_model, self.fsdp, mesh),
        )

    def replicated(self, *, prefix: int = 0) -> P:
        return P(*([None] * prefix)) if prefix else P()

    def expert_weight(self, n_exp: int, d_in: int, d_out: int, *, prefix: int = 0, mesh=None) -> P:
        """(E, d_in, d_out) expert stacks: experts on model, d_in FSDP."""
        return P(
            *([None] * prefix),
            axis_if_divisible(n_exp, self.model, mesh),
            axis_if_divisible(d_in, self.fsdp, mesh),
            None,
        )

    # --- activation constraint helpers (used inside model code) ---
    def act_btd(self, x):
        """(batch, seq, d): batch on DP axes, sequence on model (Megatron-SP)."""
        return constrain(x, self.batch, self.model, None)

    def act_btd_gathered(self, x):
        """(batch, seq, d) with sequence gathered (inside attention/mlp)."""
        return constrain(x, self.batch, None, None)

    def act_heads(self, x):
        """(batch, seq, heads, dh): shard heads on model when divisible."""
        ax = axis_if_divisible(int(x.shape[-2]), self.model)
        return constrain(x, self.batch, None, ax, None)

    def act_ecd(self, x):
        """(experts, capacity, d): experts on model (expert parallelism)."""
        ax = axis_if_divisible(int(x.shape[0]), self.model)
        return constrain(x, ax, None, None)

    def act_tokens(self, x):
        """(tokens, d): tokens on the DP axes."""
        return constrain(x, self.batch, None)

    def act_tokens_sp(self, x):
        """(tokens, d): tokens over DP axes × model (flattened batch×seq
        with Megatron-SP sequence sharding folded in — the MoE token layout)."""
        axes = (*self.batch, self.model) if self.model else self.batch
        return constrain(x, axes, None)
