"""Distributed engine (shard_map) + launch substrate on the 1-device mesh.

The 512-device production meshes are exercised by launch.dryrun (separate
process: the device-count flag must precede jax init).  Here the same code
paths run on a single-device 'engines'/(data, model) mesh — the degenerate
case — plus the HLO-parsing roofline machinery on real compiled programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partition import powerlaw_partition, random_partition
from repro.graph.algorithms import (
    bfs_program,
    pagerank_program,
    prepare_graph,
    reference_bfs,
    reference_pagerank,
    sssp_program,
    reference_sssp,
)
from repro.graph.distributed import DistributedEngine, ShardedVertexGraph, make_engines_mesh
from repro.graph.generators import rmat
from repro.graph.structs import HostGraph
from repro.launch.roofline import HW, Roofline, collective_bytes


class TestShardedGraph:
    def test_build_covers_all_vertices_and_edges(self, small_powerlaw):
        g = small_powerlaw
        p = powerlaw_partition(g.src, g.dst, g.num_nodes, 4)
        sg = ShardedVertexGraph.build(g, p)
        assert (sg.slot_to_vertex >= 0).sum() == g.num_nodes
        assert int(np.asarray(sg.valid).sum()) == g.num_edges

    def test_source_locality(self, small_powerlaw):
        """Source-cut ⇒ every edge's source property is device-local."""
        g = small_powerlaw
        p = powerlaw_partition(g.src, g.dst, g.num_nodes, 4, max_size=10**9)
        sg = ShardedVertexGraph.build(g, p)
        s2v = sg.slot_to_vertex
        valid = np.asarray(sg.valid)
        src_slot = np.asarray(sg.src_slot)
        for dev in range(4):
            vs = s2v[dev, src_slot[dev][valid[dev]]]
            assert (p.vertex_part[vs] == dev).all()


@pytest.mark.parametrize("partitioner", ["powerlaw", "random"])
class TestDistributedEngine:
    """1-engine degenerate mesh (this container has one device); the real
    multi-engine exchange is covered by test_multidevice_subprocess.py."""

    def _engine_parts(self, g, partitioner, parts=1):
        from repro.core.partition import partition_by_name

        part = partition_by_name(partitioner, g.src, g.dst, g.num_nodes, parts)
        mesh = make_engines_mesh()
        return part, mesh

    def test_bfs_matches_reference(self, small_powerlaw, partitioner):
        g = small_powerlaw
        part, mesh = self._engine_parts(g, partitioner)
        eng = DistributedEngine(bfs_program(), mesh)
        out, it = eng.run(eng.load(g, part), source=0)
        np.testing.assert_allclose(out, reference_bfs(g, 0))

    def test_sssp_matches_reference(self, small_powerlaw, partitioner):
        g = prepare_graph("sssp", small_powerlaw)
        part, mesh = self._engine_parts(g, partitioner)
        eng = DistributedEngine(sssp_program(), mesh)
        out, _ = eng.run(eng.load(g, part), source=0)
        np.testing.assert_allclose(out, reference_sssp(g, 0), rtol=1e-5)

    def test_pagerank_matches_reference(self, small_powerlaw, partitioner):
        g = prepare_graph("pagerank", small_powerlaw)
        part, mesh = self._engine_parts(g, partitioner)
        eng = DistributedEngine(pagerank_program(), mesh)
        out, _ = eng.run(eng.load(g, part), max_iterations=200)
        np.testing.assert_allclose(out, reference_pagerank(g), atol=1e-3)

    def test_bf16_compressed_exchange(self, small_powerlaw, partitioner):
        """Beyond-paper: bf16 message compression stays within tolerance."""
        g = prepare_graph("pagerank", small_powerlaw)
        part, mesh = self._engine_parts(g, partitioner)
        eng = DistributedEngine(pagerank_program(), mesh, comm_dtype=jnp.bfloat16)
        out, _ = eng.run(eng.load(g, part), max_iterations=200)
        np.testing.assert_allclose(out, reference_pagerank(g), atol=5e-2)


REFERENCES = {"bfs": reference_bfs, "sssp": reference_sssp, "pagerank": reference_pagerank}


def _resident(algorithm, monkeypatch, roots):
    """Load one seeded R-MAT graph once and answer a query from each root,
    counting the builds of the sharded graph and the traces of the step."""
    import dataclasses

    from repro.core.partition import partition_by_name

    g = rmat(400, 3200, seed=11)
    if algorithm == "sssp":
        # whole weights keep every path length exact in float32, so the
        # answer can equal the float64 reference exactly
        w = np.random.default_rng(11).integers(1, 9, g.num_edges).astype(np.float32)
        g = HostGraph(g.num_nodes, g.src, g.dst, w, g.name)
    g = prepare_graph(algorithm, g)
    builds, traces = [], []
    real_build = ShardedVertexGraph.build
    monkeypatch.setattr(ShardedVertexGraph, "build",
                        staticmethod(lambda *a: builds.append(1) or real_build(*a)))
    program = {"bfs": bfs_program, "sssp": sssp_program, "pagerank": pagerank_program}[algorithm]()
    process = program.process
    program = dataclasses.replace(program, process=lambda *a: traces.append(1) or process(*a))
    eng = DistributedEngine(program, make_engines_mesh())
    sg = eng.load(g, partition_by_name("powerlaw", g.src, g.dst, g.num_nodes, 1))
    answers = [eng.run(sg, source=r) for r in roots]
    return g, answers, len(builds), len(traces)


@pytest.mark.parametrize("algorithm", ["bfs", "sssp", "pagerank"])
def test_resident_engine_answers_query_after_query(algorithm, monkeypatch):
    """`load` once, then `run` per query: every answer equals the reference,
    the sharded graph is built once and the step is traced once."""
    roots = [0, 7, 123] if algorithm != "pagerank" else [0, 0]
    g, answers, builds, traces = _resident(algorithm, monkeypatch, roots)
    for r, (out, it) in zip(roots, answers):
        if algorithm == "pagerank":
            np.testing.assert_allclose(out, reference_pagerank(g), atol=1e-3)
        else:
            np.testing.assert_array_equal(np.asarray(out, np.float64), REFERENCES[algorithm](g, r))
        assert it >= 1
    assert builds == 1
    assert traces == 1


def test_spans_of_load_and_run():
    """`dist.load` carries the build's host time and the bytes placed on the
    mesh; each `dist.run` its waits, host time, dispatches, iterations and
    exchanged bytes.  With tracing off nothing is recorded."""
    from repro import obs
    from repro.core.partition import partition_by_name

    g = rmat(300, 2400, seed=7)
    part = partition_by_name("powerlaw", g.src, g.dst, g.num_nodes, 1)
    eng = DistributedEngine(bfs_program(), make_engines_mesh())
    tracer = obs.get_tracer()
    tracer.reset()
    sg = eng.load(g, part)
    eng.run(sg, source=0)
    assert tracer.spans() == []

    obs.enable_tracing()
    try:
        sg = eng.load(g, part)
        answers = [eng.run(sg, source=r) for r in (0, 5)]
    finally:
        obs.disable_tracing()
    spans = tracer.spans()
    tracer.reset()
    loads = [s for s in spans if s.name == "dist.load"]
    runs = [s for s in spans if s.name == "dist.run"]
    assert len(loads) == 1 and len(runs) == 2
    placed = sum(np.asarray(a).nbytes for a in (sg.src_slot, sg.dst_key, sg.weight, sg.valid))
    assert loads[0].args["host_ns"] > 0 and loads[0].args["h2d_bytes"] == placed
    for sp, (_, it) in zip(runs, answers):
        assert sp.args["iterations"] == it == sp.args["dispatches"]
        assert sp.args["wait_ns"] > 0 and sp.args["host_ns"] > 0
        assert sp.args["exchange_bytes"] == it * sg.num_devices * sg.n_local * 4


class TestRooflineMachinery:
    def test_collective_parse_on_real_hlo(self):
        """psum on a 1-device mesh emits an all-reduce; ring traffic over a
        group of 1 is zero links — the parser must report 0, not the shape."""
        mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        from jax.sharding import PartitionSpec as P

        def f(x):
            return jax.lax.psum(x, "data")

        with jax.set_mesh(mesh):
            c = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                      out_specs=P())).lower(
                jax.ShapeDtypeStruct((64, 128), jnp.float32)).compile()
        txt = c.as_text()
        assert "all-reduce" in txt
        cb = collective_bytes(txt)
        assert cb["all-reduce"] == 0.0  # group size 1 → no link traffic

    def test_shape_bytes_parser(self):
        from repro.launch.roofline import _shape_bytes

        assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
        assert _shape_bytes("(bf16[8,4], f32[2])") == 8 * 4 * 2 + 2 * 4
        assert _shape_bytes("pred[16]") == 16

    def test_ring_factors_and_groups(self):
        """Synthetic HLO: group parsing + per-op ring traffic factors."""
        from repro.launch.roofline import _group_size, _ring_factor

        assert _group_size("all-reduce(x), replica_groups={{0,1,2,3},{4,5,6,7}}", 99) == 4
        assert _group_size("all-gather(x), replica_groups=[16,16]<=[256]", 99) == 16
        assert _group_size("all-gather(x)", 7) == 7
        assert _ring_factor("all-gather", 16) == 1.0
        assert _ring_factor("all-reduce", 16) == pytest.approx(2 * 15 / 16)
        assert _ring_factor("reduce-scatter", 16) == 15.0
        hlo = (
            "  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), "
            "replica_groups={{0,1}}, to_apply=%add\n"
            "  %rs = f32[64]{0} reduce-scatter(f32[1024]{0} %y), "
            "replica_groups=[1,16]<=[16], dimensions={0}\n"
        )
        cb = collective_bytes(hlo)
        assert cb["all-reduce"] == pytest.approx(1024 * 4 * 2 * 1 / 2)
        assert cb["reduce-scatter"] == pytest.approx(64 * 4 * 15)

    def test_roofline_terms(self):
        r = Roofline(
            arch="x", cell="y", mesh="16x16", chips=256,
            hlo_flops=197e12, hlo_bytes=819e9, coll_bytes=50e9,
            coll_breakdown={}, model_flops=197e12 * 256 * 0.5,
        )
        assert r.t_compute == pytest.approx(1.0)
        assert r.t_memory == pytest.approx(1.0)
        assert r.t_collective == pytest.approx(1.0)
        assert r.roofline_fraction == pytest.approx(0.5)

    def test_mesh_smoke_helper(self):
        from repro.launch.mesh import make_smoke_mesh, mesh_devices

        m = make_smoke_mesh()
        assert mesh_devices(m) == 1


class TestDataPipelines:
    def test_token_pipeline_zipf_skew(self):
        from repro.data.pipeline import TokenPipeline

        b = next(iter(TokenPipeline(1000, 64, 32)))
        assert b["tokens"].shape == (32, 64)
        # Zipf skew: token 0 is the most frequent
        counts = np.bincount(b["tokens"].ravel(), minlength=1000)
        assert counts[0] == counts.max()

    def test_recsys_pipeline_hot_rows(self):
        from repro.data.pipeline import RecsysPipeline

        b = next(iter(RecsysPipeline(4, 6, 10_000, 512)))
        ids = b["sparse_ids"]
        assert ids.shape == (512, 6)
        counts = np.bincount(ids.ravel(), minlength=10_000)
        top = np.sort(counts)[::-1]
        # hot-row skew: top 10 of 10k rows carry >15% of lookups (uniform: 0.1%)
        assert top[:10].sum() > 0.15 * counts.sum()

    def test_graph_batcher_shapes(self, small_powerlaw):
        from repro.data.pipeline import GraphBatcher

        bt = GraphBatcher(small_powerlaw, d_feat=8, n_classes=4)
        fb = bt.full_batch(pad_edges=small_powerlaw.num_edges + 10)
        assert fb["src"].shape == (small_powerlaw.num_edges + 10,)
        mol = bt.molecule_batch(4, 10, 20)
        assert mol["labels"].shape == (4,)
        assert mol["graph_ids"].max() == 3

    def test_host_slice(self):
        from repro.data.pipeline import host_slice

        starts = [host_slice(256, i, 8) for i in range(8)]
        assert starts[0] == (0, 32) and starts[7] == (224, 32)
