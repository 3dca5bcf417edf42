"""Robustness tests for the content-hashed traffic shard cache.

The sharded path (`SweepCache.traffic(edge_block=...)`) persists one .npz per
edge block plus one vertex shard, each carrying a sha256 over its payload.
These tests lock down the failure contract: a missing, truncated, or
hash-mismatched shard file triggers recompute of ONLY that shard (never a
crash, never invalidation of its neighbours), and every degraded path still
returns a bit-exact traffic matrix.
"""
import glob
import os

import numpy as np
import pytest
from _traffic_oracle import edge_block_oracle, vertex_block_oracle

from repro.core.partition import powerlaw_partition
from repro.core.traffic import SparseTraffic, TrafficMatrix, traffic_from_partition
from repro.experiments.cache import SweepCache, _atomic_savez, _load_shard, _shard_sha
from repro.graph.generators import rmat
from repro.graph.vertex_program import TraceResult


@pytest.fixture()
def setup(tmp_path):
    g = rmat(300, 2400, seed=7)
    part = powerlaw_partition(g.src, g.dst, g.num_nodes, 4)
    rng = np.random.default_rng(7)
    trace = TraceResult(
        props=np.zeros(g.num_nodes),
        num_iterations=5,
        edge_activity=rng.integers(0, 6, size=g.src.size).astype(np.float64),
        vertex_activity=rng.integers(0, 8, size=g.num_nodes).astype(np.float64),
        frontier_sizes=[g.num_nodes] * 5,
    )
    dense = traffic_from_partition(
        part, g.src, g.dst,
        edge_activity=trace.edge_activity, vertex_activity=trace.vertex_activity,
    )
    cache = SweepCache(tmp_path)
    return g, part, trace, dense, cache, tmp_path


def _shards(root):
    return sorted(glob.glob(os.path.join(str(root), "*.shard*.npz")))


def _assert_matches(t, dense):
    assert isinstance(t, SparseTraffic)
    assert np.array_equal(t.to_dense().bytes_matrix, dense.bytes_matrix)
    assert t.phase_bytes == dense.phase_bytes


def test_cold_then_warm_round_trip(setup):
    g, part, trace, dense, cache, root = setup
    t = cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    _assert_matches(t, dense)
    # E=2400 / block 500 → 5 edge shards, + 1 vertex shard
    assert len(_shards(root)) == 6
    assert cache.stats.shard_misses == 6 and cache.stats.shard_hits == 0
    t2 = cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    _assert_matches(t2, dense)
    assert cache.stats.shard_misses == 6 and cache.stats.shard_hits == 6


def test_truncated_shard_recomputes_only_that_shard(setup):
    g, part, trace, dense, cache, root = setup
    cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    victim = _shards(root)[2]
    data = open(victim, "rb").read()
    with open(victim, "wb") as f:
        f.write(data[: len(data) // 2])
    assert _load_shard(victim) is None  # corrupt zip → None, not an exception
    before = cache.stats.shard_misses
    t = cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    _assert_matches(t, dense)
    assert cache.stats.shard_misses == before + 1  # only the victim recomputed
    assert _load_shard(victim) is not None  # and rewritten valid


def test_hash_mismatch_invalidates_only_affected_shard(setup):
    g, part, trace, dense, cache, root = setup
    cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    victim = _shards(root)[0]
    loaded = np.load(victim)
    keys, vals = loaded["keys"], loaded["vals"].copy()
    vals[0] += 8.0  # valid zip, wrong content vs stored sha
    np.savez_compressed(
        victim + ".tmp.npz", keys=keys, vals=vals,
        total=loaded["total"], sha=loaded["sha"],
    )
    os.replace(victim + ".tmp.npz", victim)
    assert _load_shard(victim) is None
    before = cache.stats.shard_misses
    t = cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    _assert_matches(t, dense)
    assert cache.stats.shard_misses == before + 1


def test_missing_shard_recomputes_only_that_shard(setup):
    g, part, trace, dense, cache, root = setup
    cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    os.remove(_shards(root)[4])
    before = cache.stats.shard_misses
    t = cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    _assert_matches(t, dense)
    assert cache.stats.shard_misses == before + 1


def test_sharded_layouts_and_single_file_path_agree(setup):
    g, part, trace, dense, cache, root = setup
    td = cache.traffic(g, part, trace, layout="dense", edge_block=500)
    assert isinstance(td, TrafficMatrix)
    assert np.array_equal(td.bytes_matrix, dense.bytes_matrix)
    ta = cache.traffic(g, part, trace, layout="auto", edge_block=500)
    assert isinstance(ta, TrafficMatrix)  # 16 shards ≤ dense hatch
    # historical single-file path, untouched by sharding
    t1 = cache.traffic(g, part, trace)
    assert isinstance(t1, TrafficMatrix)
    assert np.array_equal(t1.bytes_matrix, dense.bytes_matrix)
    assert cache.stats.traffic_misses == 1
    cache.traffic(g, part, trace)
    assert cache.stats.traffic_hits == 1


def test_uncached_sharded_compute(setup):
    g, part, trace, dense, _cache, _root = setup
    cache = SweepCache(None)  # no root → pure compute, still block-streamed
    t = cache.traffic(g, part, trace, layout="sparse", edge_block=100)
    _assert_matches(t, dense)
    assert cache.stats.shard_misses == 25  # ceil(2400/100) + 1, nothing stored


def test_shards_of_the_sort_form_still_hit(setup):
    """Shards whose payload the four-sort block form wrote (as caches filled
    before the part-pair histogram hold them) verify and hit: the histogram
    blocks hash to the same sha, and the merged matrix is unchanged."""
    g, part, trace, dense, cache, root = setup
    cache.traffic(g, part, trace, layout="sparse", edge_block=500)
    paths = _shards(root)  # edge shards 0..4 in block order, then the vertex shard
    E = g.src.size
    payloads = [
        edge_block_oracle(part, g.src, g.dst, edge_activity=trace.edge_activity,
                          packet_bytes=8, model="paper", lo=lo, hi=min(lo + 500, E))
        for lo in range(0, E, 500)
    ] + [vertex_block_oracle(part, vertex_activity=trace.vertex_activity,
                             packet_bytes=8, lo=0, hi=g.num_nodes)]
    assert len(paths) == len(payloads) == 6
    for path, (keys, vals, total) in zip(paths, payloads):
        sha = _shard_sha(keys, vals, total)
        with np.load(path) as z:
            assert str(z["sha"]) == sha
        _atomic_savez(path, keys=keys, vals=vals, total=np.float64(total), sha=np.str_(sha))
    fresh = SweepCache(root)
    _assert_matches(fresh.traffic(g, part, trace, layout="sparse", edge_block=500), dense)
    assert fresh.stats.shard_hits == 6 and fresh.stats.shard_misses == 0
