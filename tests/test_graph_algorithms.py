"""Vertex-centric engine vs pure-python references (BFS / SSSP / PageRank)."""
import numpy as np
import pytest

from repro.graph.algorithms import (
    bfs_program,
    pagerank_program,
    prepare_graph,
    reference_bfs,
    reference_pagerank,
    reference_sssp,
    sssp_program,
)
from repro.graph.generators import chung_lu, grid2d, rmat, table2_workloads, uniform_random
from repro.graph.sampler import NeighborSampler
from repro.graph.structs import build_ell, to_device_edges
from repro.graph.vertex_program import run, run_loop, run_traced, traced_step


@pytest.fixture(scope="module")
def graphs():
    return [
        rmat(120, 700, seed=0),
        uniform_random(80, 400, seed=1),
        grid2d(8, 8),
    ]


class TestAlgorithms:
    def test_bfs_matches_reference(self, graphs):
        for g in graphs:
            got = run(g, bfs_program(), source=0).props
            want = reference_bfs(g, 0)
            np.testing.assert_allclose(got, want)

    def test_sssp_matches_reference(self, graphs):
        for g in graphs:
            gw = prepare_graph("sssp", g)
            got = run(gw, sssp_program(), source=0).props
            want = reference_sssp(gw, 0)
            np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_pagerank_matches_reference(self, graphs):
        for g in graphs:
            gp = prepare_graph("pagerank", g)
            got = run(gp, pagerank_program(), source=0, max_iterations=200).props
            want = reference_pagerank(gp)
            np.testing.assert_allclose(got, want, atol=1e-4)

    def test_traced_equals_jitted(self, graphs):
        g = graphs[0]
        a = run(g, bfs_program(), source=0).props
        b = run_traced(g, bfs_program(), source=0).props
        np.testing.assert_allclose(a, b)

    def test_graph_enters_the_step_as_arguments(self):
        """Both compiled programs take the edge arrays as arguments of
        `main`; no edge list is baked into the program as a constant (a
        20k-edge constant alone would print as ~160 kB of hex)."""
        g = rmat(2000, 20_000, seed=9)
        program = pagerank_program()
        gp = prepare_graph("pagerank", g)
        e = to_device_edges(gp)
        graph = (e.src, e.dst, e.valid, e.weight)
        aux = program.make_aux(gp)
        props, active = program.init(gp.num_nodes, 0)
        for lowered in (
            traced_step.lower(program, graph, props, active, aux),
            run_loop.lower(program, 200, graph, props, active, aux),
        ):
            text = lowered.as_text()
            main = next(ln for ln in text.splitlines() if "func.func public @main" in ln)
            assert main.count("tensor<20000xi32>") == 2, main  # src, dst
            assert "tensor<20000xi1>" in main and "tensor<20000xf32>" in main, main
            assert len(text) < 40_000

    def test_padded_edges_are_inert(self, graphs):
        g = graphs[0]
        a = run(g, bfs_program(), source=0).props
        b = run(g, bfs_program(), source=0, pad_to=g.num_edges + 173).props
        np.testing.assert_allclose(a, b)


class TestGenerators:
    def test_table2_workloads_match_published_sizes(self):
        from repro.graph.generators import WORKLOADS

        wl = table2_workloads(scale=0.01)
        assert {"amazon", "soc-pokec", "wiki", "ljournal"} <= set(wl)
        for spec in WORKLOADS:
            g = wl[spec.name]
            target = max(256, int(spec.num_edges * 0.01))
            assert abs(g.num_edges - target) / target < 0.2

    def test_rmat_deterministic(self):
        a, b = rmat(100, 500, seed=5), rmat(100, 500, seed=5)
        np.testing.assert_array_equal(a.src, b.src)

    def test_chung_lu_power_law(self):
        from repro.core.degree import out_degrees, skew_stats

        g = chung_lu(2000, 30_000, alpha=2.1, seed=1)
        assert g.num_edges == 30_000
        stats = skew_stats(out_degrees(g.src, g.num_nodes))
        assert stats.frac_vertices_for_90pct_edges < 0.5  # heavy-tailed


class TestSamplerAndLayouts:
    def test_fanout_sampler_bounds(self):
        g = rmat(500, 6000, seed=2)
        s = NeighborSampler(g, fanouts=(5, 3))
        mb = s.sample(np.arange(32))
        assert mb.num_seeds == 32
        assert mb.node_ids.size <= 32 * (1 + 5 + 15)
        # edges reference local node ids
        assert mb.src.max() < mb.node_ids.size

    def test_ell_covers_all_edges(self):
        g = rmat(200, 2000, seed=3)
        ell = build_ell(g)
        total = sum(int((c != g.num_nodes).sum()) for c in ell.cols)
        assert total == g.num_edges

    def test_device_edges_padding(self):
        g = rmat(50, 300, seed=4)
        e = to_device_edges(g, pad_to=400)
        assert e.src.shape == (400,)
        assert int(e.valid.sum()) == 300
