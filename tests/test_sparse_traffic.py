"""Dense-parity property harness for the sparse-first pipeline.

The contract under test (see `repro.core.traffic` module docstring): traffic
bytes are integer-valued float64 (iteration counts × packet bytes) and hop
distances are integers, so every sparse/blocked/chunked re-association of the
dense reference computation is BIT-IDENTICAL — equality below is
`np.array_equal` / `==`, not allclose, except where a jax f32 backend is
explicitly in play (tolerances stated inline).

Covered, per random graph × all four topologies × both traffic models:
  * traffic matrices: dense single-pass vs sparse/blocked/auto layouts,
    every edge-block size, plus the `SweepCache` shard path;
  * one block's COO (`edge_block_coo`/`vertex_block_coo`, histogram or sort)
    vs the four-sort reference the shard payloads were first written in,
    byte for byte;
  * H evaluation: `sparse_weighted_hops` (+ the batched numpy/jax versions)
    vs the dense `Placement.weighted_hops`;
  * per-step swap/move deltas: `swap_delta_pairs` vs the dense
    `swap_delta_matrix`, blocked `two_opt_best_move` descent vs dense,
    `two_opt_topk(k=n)` replaying the dense search exactly;
  * chunked windows: `simulate_batch(pair_block=...)` and
    `contended_batch(window_chunk=...)` vs their unchunked runs, on both
    backends, for arbitrary chunk sizes.
"""
import contextlib
import dataclasses

from _hypothesis_compat import given, settings, st
from _traffic_oracle import edge_block_oracle, vertex_block_oracle

import numpy as np
import pytest

from repro import obs
from repro.core.noc import FlattenedButterfly, Mesh2D, Torus2D, Torus3D
from repro.core.partition import powerlaw_partition, random_partition
from repro.core.placement import (
    default_max_steps,
    random_placement,
    sparse_weighted_hops,
    swap_candidates_topk,
    swap_delta_matrix,
    swap_delta_pairs,
    two_opt_best_move,
    two_opt_topk,
)
from repro.core.traffic import (
    SparseTraffic,
    TrafficMatrix,
    edge_block_coo,
    traffic_from_partition,
    vertex_block_coo,
)
from repro.experiments.batched import simulate_batch
from repro.experiments.placement_batch import (
    batch_descend,
    sparse_weighted_hops_batch,
    swap_delta_pairs_batch,
)
from repro.graph.generators import rmat
from repro.nocsim.batch import contended_batch

# One topology per family, sized for 4P logical shards at small P.
TOPOLOGIES = {
    "mesh2d": lambda: Mesh2D(4, 4),
    "fbutterfly": lambda: FlattenedButterfly(4, 4),
    "torus2d": lambda: Torus2D(4, 4),
    "torus3d": lambda: Torus3D(2, 3, 6),
}


def _graph_and_partition(seed: int, num_parts: int = 4):
    g = rmat(200, 1600, seed=seed)
    part = powerlaw_partition(g.src, g.dst, g.num_nodes, num_parts)
    return g, part


def _activities(g, seed: int):
    rng = np.random.default_rng(seed)
    ea = rng.integers(0, 6, size=g.src.size).astype(np.float64)
    va = rng.integers(0, 8, size=g.num_nodes).astype(np.float64)
    return ea, va


class TestTrafficParity:
    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 1000),
        model=st.sampled_from(["paper", "cross"]),
        edge_block=st.sampled_from([1, 3, 17, 100, 10**6, None]),
        with_activity=st.booleans(),
    )
    def test_sparse_blocked_bitexact_vs_dense(self, seed, model, edge_block, with_activity):
        g, part = _graph_and_partition(seed)
        ea, va = _activities(g, seed) if with_activity else (None, None)
        dense = traffic_from_partition(
            part, g.src, g.dst, edge_activity=ea, vertex_activity=va, model=model
        )
        sp = traffic_from_partition(
            part, g.src, g.dst, edge_activity=ea, vertex_activity=va,
            model=model, layout="sparse", edge_block=edge_block,
        )
        assert isinstance(sp, SparseTraffic)
        assert np.array_equal(sp.to_dense().bytes_matrix, dense.bytes_matrix)
        assert sp.phase_bytes == dense.phase_bytes
        # canonical COO: identical triplets to np.nonzero of the dense matrix
        ref = dense.to_sparse()
        assert np.array_equal(sp.rows, ref.rows)
        assert np.array_equal(sp.cols, ref.cols)
        assert np.array_equal(sp.vals, ref.vals)
        # blocked dense layout is the same accumulation, materialized
        d2 = traffic_from_partition(
            part, g.src, g.dst, edge_activity=ea, vertex_activity=va,
            model=model, layout="dense", edge_block=edge_block,
        )
        assert isinstance(d2, TrafficMatrix)
        assert np.array_equal(d2.bytes_matrix, dense.bytes_matrix)

    def test_auto_layout_hatch(self):
        g, part = _graph_and_partition(0)
        t = traffic_from_partition(part, g.src, g.dst, layout="auto")
        assert isinstance(t, TrafficMatrix)  # 16 logical shards ≤ hatch

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_symmetrized_coo_matches_dense(self, seed):
        g, part = _graph_and_partition(seed)
        sp = traffic_from_partition(part, g.src, g.dst, layout="sparse")
        rows, cols, vals = sp.symmetrized_coo()
        n = sp.num_logical
        m = np.zeros((n, n))
        m[rows, cols] = vals
        assert np.array_equal(m, sp.to_dense().symmetrized())


def _spilled(g, num_parts: int, seed: int):
    """A random partition with a fifth of the edges moved off their source's
    part, as capacity spill does (part(e) ≠ part(src))."""
    part = random_partition(g.src, g.dst, g.num_nodes, num_parts, seed=seed)
    rng = np.random.default_rng(seed)
    edge_part = part.edge_part.copy()
    moved = rng.random(edge_part.size) < 0.2
    edge_part[moved] = (edge_part[moved] + rng.integers(1, num_parts, moved.sum())) % num_parts
    return dataclasses.replace(part, edge_part=edge_part)


class TestBlockHistogramVsSort:
    """`edge_block_coo`/`vertex_block_coo` reduce a block through a part-pair
    histogram (or one sort where P² passes the block); their output must be
    byte-identical to the four-sort reference the shard payloads were first
    written in, so on-disk shards and their hashes stay valid.  P = 4 makes
    blocks of 1 and 3 sort and larger ones histogram; P = 40 (1600 pair
    bins > 1200 edges) sorts every edge block."""

    N, E = 150, 1200

    @staticmethod
    @contextlib.contextmanager
    def _counting():
        obs.enable_tracing()
        try:
            with obs.span("blocks") as sp:
                yield sp
        finally:
            obs.disable_tracing()
            obs.get_tracer().reset()

    @staticmethod
    def _assert_paths(sp, total, step, bins):
        """Each block took the histogram iff its bins fit in its length."""
        sizes = [min(step, total - lo) for lo in range(0, total, step)]
        assert sp.args.get("hist_blocks", 0) == sum(bins <= k for k in sizes)
        assert sp.args.get("sort_blocks", 0) == sum(bins > k for k in sizes)

    @staticmethod
    def _assert_same(got, want):
        assert got[0].dtype == want[0].dtype and np.array_equal(got[0], want[0])
        assert got[1].dtype == want[1].dtype and got[1].tobytes() == want[1].tobytes()
        assert got[2] == want[2]

    @pytest.mark.parametrize("num_parts", [4, 40])
    @pytest.mark.parametrize("block", [1, 3, 17, None])
    @pytest.mark.parametrize("with_activity", [False, True])
    @pytest.mark.parametrize("model", ["paper", "cross"])
    def test_edge_blocks_match_sort_oracle(self, model, with_activity, block, num_parts):
        g = rmat(self.N, self.E, seed=11)
        part = _spilled(g, num_parts, seed=11)
        assert (part.edge_part != part.vertex_part[g.src]).any()
        ea = None
        if with_activity:
            ea = np.random.default_rng(11).integers(0, 4, self.E).astype(np.float64)
            ea[part.edge_part == 0] = 0.0  # part 0's keys are present with zero sums
        step = block or self.E
        zero_sums = 0
        with self._counting() as sp:
            for lo in range(0, self.E, step):
                kw = dict(edge_activity=ea, packet_bytes=8, model=model, lo=lo, hi=min(lo + step, self.E))
                got = edge_block_coo(part, g.src, g.dst, **kw)
                self._assert_same(got, edge_block_oracle(part, g.src, g.dst, **kw))
                zero_sums += int((got[1] == 0.0).sum())
        assert (zero_sums > 0) == with_activity
        self._assert_paths(sp, self.E, step, num_parts**2)

    @pytest.mark.parametrize("model", ["paper", "cross"])
    def test_empty_blocks_match_sort_oracle(self, model):
        g = rmat(self.N, self.E, seed=17)
        part = _spilled(g, 4, seed=17)
        kw = dict(edge_activity=None, packet_bytes=8, model=model, lo=5, hi=5)
        self._assert_same(edge_block_coo(part, g.src, g.dst, **kw),
                          edge_block_oracle(part, g.src, g.dst, **kw))
        kw = dict(vertex_activity=None, packet_bytes=8, lo=5, hi=5)
        self._assert_same(vertex_block_coo(part, **kw), vertex_block_oracle(part, **kw))

    @pytest.mark.parametrize("num_parts", [4, 40])
    @pytest.mark.parametrize("block", [1, 3, 17, None])
    @pytest.mark.parametrize("with_activity", [False, True])
    def test_vertex_blocks_match_sort_oracle(self, with_activity, block, num_parts):
        g = rmat(self.N, self.E, seed=13)
        part = _spilled(g, num_parts, seed=13)
        va = None
        if with_activity:
            va = np.random.default_rng(13).integers(0, 4, self.N).astype(np.float64)
            va[part.vertex_part == 0] = 0.0
        step = block or self.N
        with self._counting() as sp:
            for lo in range(0, self.N, step):
                kw = dict(vertex_activity=va, packet_bytes=8, lo=lo, hi=min(lo + step, self.N))
                self._assert_same(vertex_block_coo(part, **kw), vertex_block_oracle(part, **kw))
        self._assert_paths(sp, self.N, step, num_parts)


class TestPlacementKernelParity:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 1000), topo=st.sampled_from(sorted(TOPOLOGIES)))
    def test_sparse_h_bitexact(self, seed, topo):
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = TOPOLOGIES[topo]()
        pl = random_placement(t.num_logical, topology, seed=seed)
        w = t.symmetrized()
        rows, cols = np.nonzero(w)
        h_sparse = sparse_weighted_hops(
            rows, cols, w[rows, cols], topology.distance_matrix(), pl.site
        )
        assert h_sparse == pl.weighted_hops(w)

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 1000), topo=st.sampled_from(sorted(TOPOLOGIES)))
    def test_pair_deltas_bitexact_vs_dense_matrix(self, seed, topo):
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = TOPOLOGIES[topo]()
        pl = random_placement(t.num_logical, topology, seed=seed)
        w = t.symmetrized()
        d = topology.distance_matrix()
        site = pl.site
        dense = swap_delta_matrix(w, d, site)
        n = w.shape[0]
        iu, ju = np.triu_indices(n, k=1)
        got = swap_delta_pairs(w, d, site, iu, ju)
        assert np.array_equal(got, dense[iu, ju])

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 1000),
        topo=st.sampled_from(["mesh2d", "torus2d", "fbutterfly"]),
        block=st.sampled_from([1, 5, 17, 1000]),
    )
    def test_blocked_descent_bitidentical(self, seed, topo, block):
        """Streaming the per-step swap/move argmin over row blocks reproduces
        the dense descent step-for-step (strict-< streaming update == argmin
        first-occurrence tie-break)."""
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = TOPOLOGIES[topo]()
        init = random_placement(t.num_logical, topology, seed=seed)
        w = t.symmetrized()
        ref = two_opt_best_move(init, w)
        got = two_opt_best_move(init, w, swap_block=block)
        assert np.array_equal(got.site, ref.site)
        assert got.weighted_hops(w) == ref.weighted_hops(w)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_topk_full_k_replays_dense_search(self, seed):
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = Mesh2D(4, 4)
        init = random_placement(t.num_logical, topology, seed=seed)
        w = t.symmetrized()
        ref = two_opt_best_move(init, w)
        got = two_opt_topk(init, w, k=t.num_logical)
        assert np.array_equal(got.site, ref.site)

    def test_topk_candidates_cover_dense_at_full_k(self):
        g, part = _graph_and_partition(3)
        t = traffic_from_partition(part, g.src, g.dst)
        w = t.symmetrized()
        rows, cols = np.nonzero(w)
        n = t.num_logical
        pi, pj = swap_candidates_topk(rows, cols, w[rows, cols], n, n)
        assert np.all(pi < pj)
        # k=n makes every shard a hub, so the candidate set is all pairs
        assert pi.size == n * (n - 1) // 2
        restricted = swap_candidates_topk(rows, cols, w[rows, cols], n, 2)
        assert restricted[0].size < pi.size

    @settings(max_examples=6, deadline=None)
    @given(
        seed=st.integers(0, 1000),
        topo=st.sampled_from(sorted(TOPOLOGIES)),
        block=st.sampled_from([1, 5, 13, 100]),
    )
    def test_batched_blocked_descent_bitidentical(self, seed, topo, block):
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = TOPOLOGIES[topo]()
        init = random_placement(t.num_logical, topology, seed=seed)
        w = t.symmetrized()
        steps = default_max_steps(t.num_logical)
        ref, _ = batch_descend([w], [topology], [init.site],
                               max_steps=steps, backend="numpy")
        got, _ = batch_descend([w], [topology], [init.site],
                               max_steps=steps, backend="numpy", swap_block=block)
        assert np.array_equal(got[0], ref[0])

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 1000), topo=st.sampled_from(sorted(TOPOLOGIES)))
    def test_sparse_h_batch_both_backends(self, seed, topo):
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = TOPOLOGIES[topo]()
        pl = random_placement(t.num_logical, topology, seed=seed)
        w = t.symmetrized()
        rows, cols = np.nonzero(w)
        coo = (rows, cols, w[rows, cols])
        sites = [pl.site]
        ref = pl.weighted_hops(w)
        h_np, b = sparse_weighted_hops_batch([coo], sites, [topology], backend="numpy")
        assert b == "numpy" and h_np[0] == ref
        h_jx, b = sparse_weighted_hops_batch([coo], sites, [topology], backend="jax")
        if b == "jax":  # container has jax; f32 max-normalized contraction
            assert abs(h_jx[0] - ref) / max(abs(ref), 1e-300) < 1e-5

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 1000), topo=st.sampled_from(sorted(TOPOLOGIES)))
    def test_pair_deltas_batch_both_backends(self, seed, topo):
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = TOPOLOGIES[topo]()
        pl = random_placement(t.num_logical, topology, seed=seed)
        w = t.symmetrized()
        d = topology.distance_matrix()
        site = pl.site
        n = w.shape[0]
        iu, ju = np.triu_indices(n, k=1)
        ref = swap_delta_matrix(w, d, site)[iu, ju]
        # the batch kernel takes RAW weights and symmetrizes internally
        raw = t.bytes_matrix
        got_np, b = swap_delta_pairs_batch([raw], [topology], [site], [(iu, ju)],
                                           backend="numpy")
        assert b == "numpy" and np.array_equal(got_np[0], ref)
        got_jx, b = swap_delta_pairs_batch([raw], [topology], [site], [(iu, ju)],
                                           backend="jax")
        if b == "jax":
            scale = max(np.abs(ref).max(), 1.0)
            assert np.max(np.abs(got_jx[0] - ref)) / scale < 1e-4


class TestChunkedWindows:
    def _configs(self, seed):
        g, part = _graph_and_partition(seed)
        t = traffic_from_partition(part, g.src, g.dst)
        topology = Mesh2D(4, 4)
        pl = random_placement(t.num_logical, topology, seed=seed)
        return t, pl

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 1000),
        block=st.sampled_from([1, 17, 300, 10**7]),
        sparse_input=st.booleans(),
    )
    def test_simulate_batch_pair_block_bitexact(self, seed, block, sparse_input):
        t, pl = self._configs(seed)
        traffic = t.to_sparse() if sparse_input else t
        ref = simulate_batch([t], [pl], backend="numpy")[0]
        got = simulate_batch([traffic], [pl], backend="numpy", pair_block=block)[0]
        for f in ("exec_time_s", "energy_j", "avg_hops", "byte_hops", "total_bytes"):
            assert getattr(got, f) == getattr(ref, f), f

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 1000), chunk=st.sampled_from([1, 3, 7, 64, 1000]))
    def test_contended_window_chunks_bitexact_both_backends(self, seed, chunk):
        t, pl = self._configs(seed)
        for backend in ("numpy", "jax"):
            try:
                ref = contended_batch([t], [pl], backend=backend)[0]
            except Exception:
                if backend == "jax":
                    pytest.skip("jax unavailable")
                raise
            got = contended_batch([t], [pl], backend=backend, window_chunk=chunk)[0]
            # The chunked recursion resumes from the carried backlog, which is
            # exactly the unchunked state at the boundary — bit-identical even
            # on the f32 jax backend (f32→f64→f32 carry round-trips losslessly).
            assert got.t_network_contended_s == ref.t_network_contended_s
            assert got.peak_window_util == ref.peak_window_util
            assert got.backlogged_window_frac == ref.backlogged_window_frac
