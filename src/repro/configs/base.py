"""Architecture registry substrate: families, shape cells, dry-run cases.

Every assigned architecture is one module in repro.configs that builds an
`Arch` (LmArch / GnnArch / RecsysArch).  An Arch knows:

  * its exact published configuration (the assignment block numbers),
  * its shape cells (family-specific: train/prefill/decode for LMs, graph
    layouts for GNNs, batch regimes for recsys),
  * how to produce a `DryrunCase` — the jittable step fn + ShapeDtypeStruct
    argument tree + input shardings for `launch.dryrun` to lower/compile,
  * a reduced `smoke_config()` the CPU test-suite can actually run,
  * `model_flops(cell)` — the useful-FLOPs yardstick for §Roofline
    (6·N·D train / 2·N·D forward; MoE counts active params only).

No jax arrays are materialised here: parameter/optimizer trees come from
`jax.eval_shape`, so building a 34B-param dry-run case is instant.
"""
from __future__ import annotations

import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import gnn as gnn_lib
from repro.models import recsys as rec_lib
from repro.models import transformer as tfm
from repro.models.moe import MoEConfig
from repro.models.sharding import MeshRules, axis_if_divisible
from repro.train import optim as optim_lib
from repro.train.loop import TrainState

__all__ = [
    "LM_SHAPES", "GNN_SHAPES", "RECSYS_SHAPES",
    "DryrunCase", "Arch", "LmArch", "GnnArch", "RecsysArch",
]

# ------------------------------- shape cells --------------------------------

LM_SHAPES: dict[str, tuple[str, int, int]] = {
    # name: (step kind, seq_len, global_batch)
    "train_4k": ("train", 4_096, 256),
    "prefill_32k": ("prefill", 32_768, 32),
    "decode_32k": ("decode", 32_768, 128),
    "long_500k": ("long_decode", 524_288, 1),
}

GNN_SHAPES: dict[str, dict] = {
    "full_graph_sm": dict(n_nodes=2_708, n_edges=10_556, d_feat=1_433),
    "minibatch_lg": dict(
        n_nodes=232_965, n_edges=114_615_892, batch_nodes=1_024, fanout=(15, 10), d_feat=602
    ),
    "ogb_products": dict(n_nodes=2_449_029, n_edges=61_859_140, d_feat=100),
    "molecule": dict(n_nodes=30, n_edges=64, batch=128, d_feat=32),
}

RECSYS_SHAPES: dict[str, dict] = {
    "train_batch": dict(batch=65_536, kind="train"),
    "serve_p99": dict(batch=512, kind="serve"),
    "serve_bulk": dict(batch=262_144, kind="serve"),
    "retrieval_cand": dict(batch=1, n_candidates=1_000_000, kind="retrieval"),
}

N_CLASSES_DEFAULT = 16  # synthetic label space for GNN cells


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


@dataclasses.dataclass
class DryrunCase:
    """Everything launch.dryrun needs to lower one (arch × shape × mesh)."""

    arch: str
    cell: str
    fn: typing.Callable
    args: tuple  # pytree of ShapeDtypeStruct
    in_shardings: tuple  # parallel pytree of NamedSharding (or None)
    donate_argnums: tuple = ()
    model_flops: float = 0.0  # useful FLOPs (6ND / 2ND)
    note: str = ""

    def lower(self, mesh):
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                self.fn, in_shardings=self.in_shardings, donate_argnums=self.donate_argnums
            )
            return jitted.lower(*self.args)


class Arch:
    """Interface every assigned architecture implements."""

    name: str
    family: str
    paper_technique_applies: bool
    applicability_note: str = ""

    def shape_cells(self) -> list[str]:
        raise NotImplementedError

    def skipped_cells(self) -> dict[str, str]:
        return {}

    def dryrun_case(self, cell: str, mesh, *, multi_pod: bool) -> DryrunCase:
        raise NotImplementedError

    def smoke_config(self):
        raise NotImplementedError


# ----------------------------------- LM -------------------------------------


def _opt_specs_like(param_specs_tree):
    """AdamW state (mu, nu) inherits the param sharding."""
    return {"mu": param_specs_tree, "nu": param_specs_tree}


@dataclasses.dataclass
class LmArch(Arch):
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    moe: MoEConfig | None = None
    d_head: int | None = None
    source: str = ""
    family: str = "lm"

    def __post_init__(self):
        self.paper_technique_applies = self.moe is not None
        self.applicability_note = (
            "expert placement + all-to-all mapping (hot experts ≡ hubs)"
            if self.moe is not None
            else "dense LM: uniform static collectives — no skew to exploit; "
            "standard DP×TP sharding, no paper technique (DESIGN.md §4)"
        )

    # ---------------- configs ----------------

    def model_config(self, *, multi_pod: bool = False, dryrun: bool = True) -> tfm.TransformerConfig:
        moe = self.moe
        if moe is not None and dryrun:
            moe = dataclasses.replace(moe, impl="ep_shardmap")
        return tfm.TransformerConfig(
            self.name,
            n_layers=self.n_layers,
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            d_ff=self.d_ff,
            vocab=self.vocab,
            d_head=self.d_head,
            moe=moe,
            rules=MeshRules(multi_pod=multi_pod),
        )

    def smoke_config(self) -> tfm.TransformerConfig:
        moe = self.moe
        if moe is not None:
            moe = dataclasses.replace(
                moe, num_experts=min(8, moe.num_experts), d_ff_expert=64,
                d_ff_shared=64 if moe.d_ff_shared else 0, impl="local",
            )
        return tfm.TransformerConfig(
            self.name + "-smoke",
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(4, self.n_kv_heads)),
            d_ff=128,
            vocab=512,
            moe=moe,
            dtype=jnp.float32,
        )

    def shape_cells(self) -> list[str]:
        return [c for c in LM_SHAPES if c not in self.skipped_cells()]

    def skipped_cells(self) -> dict[str, str]:
        return {
            "long_500k": "pure full-attention arch — long_500k skipped per "
            "assignment rule (DESIGN.md §long_500k)"
        }

    # ---------------- dry-run ----------------

    def model_flops(self, cell: str) -> float:
        kind, seq, batch = LM_SHAPES[cell]
        cfg = self.model_config()
        n = cfg.num_active_params
        if kind == "train":
            return 6.0 * n * seq * batch
        if kind == "prefill":
            return 2.0 * n * seq * batch
        return 2.0 * n * batch  # decode: one token per sequence

    def dryrun_case(
        self, cell: str, mesh, *, multi_pod: bool,
        n_layers: int | None = None, scan_layers: bool | None = None,
        cfg_transform: typing.Callable | None = None,
    ) -> DryrunCase:
        """n_layers/scan_layers overrides exist for the L1/L2 unroll
        calibration that corrects XLA's count-scan-body-once cost analysis
        (launch.dryrun).  cfg_transform is the §Perf hillclimb hook."""
        kind, seq, batch = LM_SHAPES[cell]
        cfg = self.model_config(multi_pod=multi_pod)
        if n_layers is not None:
            cfg = dataclasses.replace(cfg, n_layers=n_layers)
        if scan_layers is not None:
            cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
        if cfg_transform is not None:
            cfg = cfg_transform(cfg)
        r = cfg.rules
        pspecs = tfm.param_specs(cfg, mesh)
        params_s = jax.eval_shape(functools.partial(tfm.init_params, cfg), jax.random.key(0))
        params_sh = _named(mesh, pspecs)
        dp = P(r.batch, None)

        if kind == "train":
            opt = optim_lib.adamw(optim_lib.cosine_schedule(3e-4, 100, 10_000))
            opt_s = jax.eval_shape(opt.init, params_s)
            state_s = TrainState(params_s, opt_s, jax.ShapeDtypeStruct((), jnp.int32), None)
            state_sh = TrainState(
                params_sh, _named(mesh, _opt_specs_like(pspecs)), NamedSharding(mesh, P()), None
            )
            batch_s = {
                "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            }
            batch_sh = {"tokens": NamedSharding(mesh, dp), "labels": NamedSharding(mesh, dp)}

            def train_step(state, b):
                loss, grads = jax.value_and_grad(lambda p: tfm.loss_fn(p, b, cfg))(state.params)
                new_p, new_o = opt.update(grads, state.opt_state, state.params, state.step)
                return TrainState(new_p, new_o, state.step + 1, None), {"loss": loss}

            return DryrunCase(
                self.name, cell, train_step, (state_s, batch_s), (state_sh, batch_sh),
                donate_argnums=(0,), model_flops=self.model_flops(cell),
            )

        cache_len = seq if kind != "prefill" else seq
        cache_s = jax.eval_shape(
            functools.partial(tfm.init_kv_cache, cfg, batch, cache_len), )
        cache_sh = _named(mesh, tfm.kv_cache_specs(cfg, mesh))

        if kind == "prefill":
            def prefill_step(p, toks, cache):
                return tfm.prefill(p, toks, cache, cfg)

            toks_s = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
            return DryrunCase(
                self.name, cell, prefill_step,
                (params_s, toks_s, cache_s),
                (params_sh, NamedSharding(mesh, dp), cache_sh),
                donate_argnums=(2,), model_flops=self.model_flops(cell),
            )

        # decode / long_decode: one new token against a cache of `seq`
        def decode(p, cache, pos, toks):
            return tfm.decode_step(p, cache, pos, toks, cfg)

        toks_s = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        pos_s = jax.ShapeDtypeStruct((), jnp.int32)
        return DryrunCase(
            self.name, cell, decode,
            (params_s, cache_s, pos_s, toks_s),
            (params_sh, cache_sh, NamedSharding(mesh, P()), NamedSharding(mesh, dp)),
            donate_argnums=(1,), model_flops=self.model_flops(cell),
        )


# ----------------------------------- GNN ------------------------------------


@dataclasses.dataclass
class GnnArch(Arch):
    name: str
    kind: str  # gin | gat | pna | graphcast
    n_layers: int
    d_hidden: int
    n_heads: int = 1
    aggregators: tuple[str, ...] = ("sum",)
    scalers: tuple[str, ...] = ("identity",)
    mesh_refinement: int = 6
    n_vars: int = 227
    source: str = ""
    family: str = "gnn"
    paper_technique_applies: bool = True
    applicability_note: str = "vertex-centric substrate — partitioning/placement apply directly"

    def model_config(self, cell: str, *, multi_pod: bool = False) -> gnn_lib.GnnConfig:
        sh = GNN_SHAPES[cell]
        d_feat = sh["d_feat"]
        task = "graph_class" if cell == "molecule" else "node_class"
        d_out = N_CLASSES_DEFAULT
        if self.kind == "graphcast":
            task, d_out = "regression", self.n_vars
        return gnn_lib.GnnConfig(
            self.name,
            self.kind,
            n_layers=self.n_layers,
            d_hidden=self.d_hidden,
            d_in=d_feat,
            d_out=d_out,
            task=task,
            n_heads=self.n_heads,
            aggregators=self.aggregators,
            scalers=self.scalers,
            mesh_refinement=self.mesh_refinement,
            n_vars=self.n_vars,
            rules=MeshRules(multi_pod=multi_pod),
        )

    def smoke_config(self) -> gnn_lib.GnnConfig:
        return gnn_lib.GnnConfig(
            self.name + "-smoke", self.kind, n_layers=2, d_hidden=16, d_in=8,
            d_out=4, task="regression" if self.kind == "graphcast" else "node_class",
            n_heads=min(2, self.n_heads), aggregators=self.aggregators,
            scalers=self.scalers, n_vars=4,
        )

    def shape_cells(self) -> list[str]:
        return list(GNN_SHAPES)

    def model_flops(self, cell: str) -> float:
        sh = GNN_SHAPES[cell]
        cfg = self.model_config(cell)
        n_nodes = sh["n_nodes"] * sh.get("batch", 1)
        n_edges = sh["n_edges"] * sh.get("batch", 1)
        d = self.d_hidden
        # 6 × (dense param-FLOPs on nodes + message FLOPs on edges)
        return 6.0 * (cfg.num_params * 1.0 * n_nodes / max(cfg.d_in, 1) + n_edges * d)

    # ---- batch spec builders ----

    def _node_edge_counts(self, cell: str, n_devices: int) -> tuple[int, int]:
        sh = GNN_SHAPES[cell]
        if cell == "molecule":
            n = sh["n_nodes"] * sh["batch"]
            e = sh["n_edges"] * sh["batch"]
        elif cell == "minibatch_lg":
            seeds, (f1, f2) = sh["batch_nodes"], sh["fanout"]
            n = seeds * (1 + f1 + f1 * f2)
            e = seeds * (f1 + f1 * f2)
        else:
            n, e = sh["n_nodes"], sh["n_edges"]
        return _round_up(n, n_devices), _round_up(e, n_devices)

    def batch_specs(self, cell: str, n_devices: int) -> tuple[dict, dict]:
        """(ShapeDtypeStruct dict, PartitionSpec dict) for one cell."""
        sh = GNN_SHAPES[cell]
        n, e = self._node_edge_counts(cell, n_devices)
        d_feat = sh["d_feat"]
        flat = P(("pod", "data", "model"))  # cleaned by NamedSharding per mesh
        f32, i32 = jnp.float32, jnp.int32
        if self.kind == "graphcast":
            plan = gnn_lib.graphcast_mesh_plan(n, self.mesh_refinement)
            m = _round_up(plan["n_mesh"], n_devices)
            eg, em, emg = (
                _round_up(plan["e_g2m"], n_devices),
                _round_up(plan["e_m2m"], n_devices),
                _round_up(plan["e_m2g"], n_devices),
            )
            specs = {
                "x": jax.ShapeDtypeStruct((n, d_feat), f32),
                "mesh_x": jax.ShapeDtypeStruct((m, 3), f32),
                "labels": jax.ShapeDtypeStruct((n, self.n_vars), f32),
                "node_mask": jax.ShapeDtypeStruct((n,), jnp.bool_),
            }
            parts = {"x": flat, "mesh_x": flat, "labels": flat, "node_mask": flat}
            for pre, ecount in (("g2m", eg), ("m2m", em), ("m2g", emg)):
                specs[f"{pre}_src"] = jax.ShapeDtypeStruct((ecount,), i32)
                specs[f"{pre}_dst"] = jax.ShapeDtypeStruct((ecount,), i32)
                specs[f"{pre}_feat"] = jax.ShapeDtypeStruct((ecount, 4), f32)
                specs[f"{pre}_mask"] = jax.ShapeDtypeStruct((ecount,), jnp.bool_)
                for k in ("src", "dst", "feat", "mask"):
                    parts[f"{pre}_{k}"] = flat
            return specs, parts
        specs = {
            "x": jax.ShapeDtypeStruct((n, d_feat), f32),
            "src": jax.ShapeDtypeStruct((e,), i32),
            "dst": jax.ShapeDtypeStruct((e,), i32),
            "edge_mask": jax.ShapeDtypeStruct((e,), jnp.bool_),
            "node_mask": jax.ShapeDtypeStruct((n,), jnp.bool_),
        }
        parts = {k: flat for k in specs}
        if cell == "molecule":
            n_graphs = sh["batch"]
            specs["graph_ids"] = jax.ShapeDtypeStruct((n,), i32)
            specs["labels"] = jax.ShapeDtypeStruct((n_graphs,), i32)
            # graph-level labels: 128 graphs can't split 256 ways — DP axes only
            parts["graph_ids"], parts["labels"] = flat, P(("pod", "data"))
        else:
            specs["labels"] = jax.ShapeDtypeStruct((n,), i32)
            specs["train_mask"] = jax.ShapeDtypeStruct((n,), jnp.bool_)
            parts["labels"], parts["train_mask"] = flat, flat
        return specs, parts

    def dryrun_case(self, cell: str, mesh, *, multi_pod: bool,
                    cfg_transform: typing.Callable | None = None) -> DryrunCase:
        n_devices = int(np.prod(list(mesh.shape.values())))
        cfg = self.model_config(cell, multi_pod=multi_pod)
        if cfg_transform is not None:
            cfg = cfg_transform(cfg)
        params_s = jax.eval_shape(
            functools.partial(gnn_lib.init_params, cfg), jax.random.key(0)
        )
        # GNN params are small — replicate (the graph arrays carry the scale)
        params_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params_s)
        batch_s, batch_p = self.batch_specs(cell, n_devices)
        batch_sh = {k: NamedSharding(mesh, _clean(mesh, v)) for k, v in batch_p.items()}
        opt = optim_lib.adamw(optim_lib.cosine_schedule(1e-3, 100, 10_000))
        opt_s = jax.eval_shape(opt.init, params_s)
        opt_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), opt_s)
        state_s = TrainState(params_s, opt_s, jax.ShapeDtypeStruct((), jnp.int32), None)
        state_sh = TrainState(params_sh, opt_sh, NamedSharding(mesh, P()), None)

        def train_step(state, b):
            loss, grads = jax.value_and_grad(lambda p: gnn_lib.loss_fn(p, b, cfg))(state.params)
            new_p, new_o = opt.update(grads, state.opt_state, state.params, state.step)
            return TrainState(new_p, new_o, state.step + 1, None), {"loss": loss}

        return DryrunCase(
            self.name, cell, train_step, (state_s, batch_s), (state_sh, batch_sh),
            donate_argnums=(0,), model_flops=self.model_flops(cell),
        )


def _clean(mesh, spec: P) -> P:
    """Drop axis names the mesh doesn't have (e.g. 'pod' single-pod)."""
    out = []
    names = set(mesh.axis_names)
    for s in spec:
        if s is None or isinstance(s, str):
            out.append(s if s in names else None)
        else:
            kept = tuple(a for a in s if a in names)
            out.append(kept if kept else None)
    return P(*out)


# ---------------------------------- recsys ----------------------------------


@dataclasses.dataclass
class RecsysArch(Arch):
    name: str
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 16
    rows_per_table: int = 1_000_000
    n_cross_layers: int = 3
    mlp_dims: tuple[int, ...] = (1024, 1024, 512)
    source: str = ""
    family: str = "recsys"
    paper_technique_applies: bool = True
    applicability_note: str = (
        "embedding-row access is power-law — row partitioning + hot-row "
        "replication are Algorithm 2 + hub replication on lookup traffic"
    )

    def model_config(self, *, multi_pod: bool = False) -> rec_lib.DcnConfig:
        return rec_lib.DcnConfig(
            self.name,
            n_dense=self.n_dense,
            n_sparse=self.n_sparse,
            embed_dim=self.embed_dim,
            rows_per_table=self.rows_per_table,
            n_cross_layers=self.n_cross_layers,
            mlp_dims=self.mlp_dims,
            rules=MeshRules(multi_pod=multi_pod),
        )

    def smoke_config(self) -> rec_lib.DcnConfig:
        return rec_lib.DcnConfig(
            self.name + "-smoke", n_dense=4, n_sparse=6, embed_dim=8,
            rows_per_table=128, n_cross_layers=2, mlp_dims=(32, 16),
        )

    def shape_cells(self) -> list[str]:
        return list(RECSYS_SHAPES)

    def model_flops(self, cell: str) -> float:
        sh = RECSYS_SHAPES[cell]
        cfg = self.model_config()
        d0 = cfg.d_input
        dense_params = cfg.num_params - cfg.n_sparse * cfg.rows_per_table * cfg.embed_dim
        per_ex = 2.0 * dense_params + 2.0 * cfg.n_sparse * cfg.embed_dim
        mult = 6.0 if sh.get("kind") == "train" else 2.0
        flops = mult * per_ex * sh["batch"]
        if sh.get("kind") == "retrieval":
            flops += 2.0 * sh["n_candidates"] * cfg.mlp_dims[-1] * sh["batch"]
        return flops

    def dryrun_case(self, cell: str, mesh, *, multi_pod: bool,
                    cfg_transform: typing.Callable | None = None) -> DryrunCase:
        sh = RECSYS_SHAPES[cell]
        cfg = self.model_config(multi_pod=multi_pod)
        if cfg_transform is not None:
            cfg = cfg_transform(cfg)
        r = cfg.rules
        params_s = jax.eval_shape(functools.partial(rec_lib.init_params, cfg), jax.random.key(0))
        params_sh = _named(mesh, rec_lib.param_specs(cfg, mesh))
        b = sh["batch"]
        n_dev_dp = int(np.prod([mesh.shape[a] for a in mesh.axis_names if a != "model"]))
        bspec = P(r.batch) if b % n_dev_dp == 0 else P()  # retrieval: B=1 → replicate
        dp = NamedSharding(mesh, bspec)
        dp2 = NamedSharding(mesh, P(*bspec, None))
        batch_s = {
            "dense": jax.ShapeDtypeStruct((b, cfg.n_dense), jnp.float32),
            "sparse_ids": jax.ShapeDtypeStruct((b, cfg.n_sparse), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b,), jnp.float32),
        }
        batch_sh = {"dense": dp2, "sparse_ids": dp2, "labels": dp}

        if sh.get("kind") == "train":
            opt = optim_lib.adamw(optim_lib.cosine_schedule(1e-3, 100, 10_000))
            opt_s = jax.eval_shape(opt.init, params_s)
            opt_sh = {"mu": params_sh, "nu": params_sh}
            state_s = TrainState(params_s, opt_s, jax.ShapeDtypeStruct((), jnp.int32), None)
            state_sh = TrainState(params_sh, opt_sh, NamedSharding(mesh, P()), None)

            def train_step(state, bb):
                loss, grads = jax.value_and_grad(lambda p: rec_lib.loss_fn(p, bb, cfg))(
                    state.params
                )
                new_p, new_o = opt.update(grads, state.opt_state, state.params, state.step)
                return TrainState(new_p, new_o, state.step + 1, None), {"loss": loss}

            return DryrunCase(
                self.name, cell, train_step, (state_s, batch_s), (state_sh, batch_sh),
                donate_argnums=(0,), model_flops=self.model_flops(cell),
            )

        if sh.get("kind") == "retrieval":
            n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
            n_cand = _round_up(sh["n_candidates"], n_dev)  # 1M → next ×512
            d_emb = cfg.mlp_dims[-1]
            cand_s = jax.ShapeDtypeStruct((n_cand, d_emb), jnp.float32)
            cand_sh = NamedSharding(mesh, _clean(mesh, P(("pod", "data", "model"), None)))

            def retrieve(p, bb, cand):
                return rec_lib.retrieval_scores(p, bb, cand, cfg)

            return DryrunCase(
                self.name, cell, retrieve, (params_s, batch_s, cand_s),
                (params_sh, batch_sh, cand_sh), model_flops=self.model_flops(cell),
            )

        def serve(p, bb):
            return rec_lib.forward(p, bb, cfg)

        return DryrunCase(
            self.name, cell, serve, (params_s, batch_s), (params_sh, batch_sh),
            model_flops=self.model_flops(cell),
        )
