"""Multi-device integration tests, run in a subprocess so the
--xla_force_host_platform_device_count flag can precede jax's first init
(the in-process suite keeps the 1-device view by design)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str, devices: int = 8, timeout: int = 560) -> str:
    # The host-device flag is APPENDED to any inherited XLA_FLAGS (not
    # clobbered) and must precede jax's first import.
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count={devices}").strip()
        {textwrap.indent(textwrap.dedent(body), '        ').lstrip()}
        print("SUBPROCESS_OK")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "SUBPROCESS_OK" in out.stdout
    return out.stdout


@pytest.mark.slow
def test_distributed_bfs_pagerank_8_engines():
    run_subprocess("""
        import numpy as np, jax
        from repro.core.partition import powerlaw_partition
        from repro.core.mapping import DeviceMapper
        from repro.graph.generators import rmat
        from repro.graph.algorithms import (bfs_program, pagerank_program,
            prepare_graph, reference_bfs, reference_pagerank)
        from repro.graph.distributed import DistributedEngine, make_engines_mesh

        g = rmat(200, 1600, seed=5)
        part = powerlaw_partition(g.src, g.dst, g.num_nodes, 8)
        # paper placement: permute engines by the DeviceMapper
        perm, *_ = DeviceMapper((2, 4)).device_permutation(g.src, g.dst, g.num_nodes)
        mesh = make_engines_mesh(site_permutation=perm)
        out, it = DistributedEngine(bfs_program(), mesh).run(g, part, source=0)
        np.testing.assert_allclose(out, reference_bfs(g, 0))

        gp = prepare_graph("pagerank", g)
        out, _ = DistributedEngine(pagerank_program(), mesh).run(gp, part)
        np.testing.assert_allclose(out, reference_pagerank(gp), atol=1e-3)
    """)


@pytest.mark.slow
def test_moe_ep_shardmap_equals_local_2x4():
    run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.models import moe as moe_lib
        from repro.models.sharding import MeshRules
        kw = dict(num_experts=8, top_k=2, d_ff_expert=64, d_ff_shared=16,
                  capacity_factor=4.0)
        m_l = moe_lib.MoEConfig(**kw, impl="local")
        m_e = moe_lib.MoEConfig(**kw, impl="ep_shardmap")
        shapes = moe_lib.layer_shapes(m_l, 32)
        ks = jax.random.split(jax.random.key(0), len(shapes) + 1)
        lp = {n: jax.random.normal(k, s, jnp.float32) * 0.05
              for (n, s), k in zip(shapes.items(), ks)}
        x = jax.random.normal(ks[-1], (4, 16, 32), jnp.float32)
        r = MeshRules()
        ref = moe_lib.moe_block(m_l, lp, x, rules=r)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda lp, x: moe_lib.moe_block(m_e, lp, x, rules=r))(lp, x)
            txt = jax.jit(lambda lp, x: moe_lib.moe_block(m_e, lp, x, rules=r)
                          ).lower(lp, x).compile().as_text()
        assert float(jnp.abs(out - ref).max()) < 2e-5
        assert "all-to-all" in txt  # EP really exchanges tokens
    """)


@pytest.mark.slow
def test_halo_gin_equals_global_8_engines():
    """§Perf cell 2 machinery: Algorithm-2 partition + destination-cut +
    halo all_to_all equals the global segment_sum formulation."""
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.graph.generators import rmat
        from repro.graph.halo import build_halo_plan
        from repro.models import gnn as gnn_lib
        from repro.models.gnn_dist import gin_forward_halo, pack_batch

        g = rmat(120, 900, seed=4)
        cfg = gnn_lib.GnnConfig("gin", "gin", n_layers=3, d_hidden=16, d_in=8, d_out=5)
        params = gnn_lib.init_params(cfg, jax.random.key(0))
        x = np.asarray(jax.random.normal(jax.random.key(1), (120, 8)))
        labels = np.random.default_rng(0).integers(0, 5, 120)
        batch_ref = dict(x=jnp.asarray(x), src=jnp.asarray(g.src.astype(np.int32)),
                         dst=jnp.asarray(g.dst.astype(np.int32)),
                         edge_mask=jnp.ones(g.num_edges, bool),
                         node_mask=jnp.ones(120, bool),
                         labels=jnp.asarray(labels), train_mask=jnp.ones(120, bool))
        ref = gnn_lib.forward(params, batch_ref, cfg)
        plan = build_halo_plan(g.src, g.dst, 120, 8)
        batch = {k: jnp.asarray(v) for k, v in
                 pack_batch(plan, x, labels, np.ones(120, bool)).items()}
        mesh = Mesh(np.asarray(jax.devices()), ("engines",))
        with jax.set_mesh(mesh):
            out = jax.jit(lambda p, b: gin_forward_halo(p, b, cfg, mesh))(params, batch)
        got = np.zeros((120, 5), np.float32)
        ok = plan.slot_to_vertex >= 0
        got[plan.slot_to_vertex[ok]] = np.asarray(out)[ok]
        assert float(np.abs(got - np.asarray(ref)).max()) < 2e-4
    """)


@pytest.mark.slow
def test_sharded_transformer_train_step_2x2():
    """Megatron TP + DP on 2×2: loss finite, params sharded as specced,
    and the gradient all-reduce is present in the HLO."""
    run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import transformer as tfm
        from repro.models.sharding import MeshRules
        from repro.train.optim import adamw

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = tfm.TransformerConfig("t", n_layers=2, d_model=64, n_heads=4,
                                    n_kv_heads=2, d_ff=128, vocab=128,
                                    dtype=jnp.float32,
                                    rules=MeshRules())
        params = tfm.init_params(cfg, jax.random.key(0))
        specs = tfm.param_specs(cfg, mesh)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
            is_leaf=lambda x: hasattr(x, "shape"))
        toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 128)
        batch = {"tokens": toks, "labels": toks}
        opt = adamw(1e-3)

        def step(p, b):
            loss, g = jax.value_and_grad(lambda pp: tfm.loss_fn(pp, b, cfg))(p)
            newp, _ = opt.update(g, opt.init(p), p, 0)
            return loss, newp

        with jax.set_mesh(mesh):
            jitted = jax.jit(step)
            loss, newp = jitted(params, batch)
            txt = jitted.lower(params, batch).compile().as_text()
        assert jnp.isfinite(loss)
        assert "all-reduce" in txt
        # weight stays sharded through the update
        assert newp["layers"]["w_gate"].sharding.spec == specs["layers"]["w_gate"]
    """, devices=4)
