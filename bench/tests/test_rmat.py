import numpy as np

from bench.generators.rmat import rmat_edges

GRAPH500 = dict(a=0.57, b=0.19, c=0.19)


def test_stated_sizes_and_seed():
    n, e = 40_000, 765_000  # soc-Pokec's |V| and |E| at 2.5 %
    src, dst = rmat_edges(n, e, seed=2**40 + 3, **GRAPH500)
    assert src.shape == dst.shape == (e,)
    assert src.dtype == dst.dtype == np.int64
    assert 0 <= min(src.min(), dst.min()) and max(src.max(), dst.max()) < n
    again = rmat_edges(n, e, seed=2**40 + 3, **GRAPH500)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    other = rmat_edges(n, e, seed=2**40 + 4, **GRAPH500)
    assert not np.array_equal(src, other[0])


def test_skew_as_the_repositorys_rmat():
    """Fig. 4 reports that at most 10 % of the SNAP graphs' vertices are the
    sources of 90 % of their edges.  The R-MAT stand-in is less skewed than
    that (the repository's own generator too): at 2.5 % of soc-Pokec its top
    10 % of vertices are the sources of about 77 % of the edges, and 90 %
    takes about 22 % of the vertices.  This generator keeps that
    distribution: both shares match the repository's generator within a
    point, from a seed."""
    from repro.core.degree import out_degrees, skew_stats
    from repro.graph.generators import rmat

    n, e = 40_000, 765_000
    src, _ = rmat_edges(n, e, seed=12345, **GRAPH500)
    ours = skew_stats(out_degrees(src, n))
    theirs = skew_stats(out_degrees(rmat(n, e, seed=12345).src, n))
    assert ours.frac_edges_in_top10pct_vertices >= 0.75
    assert ours.frac_vertices_for_90pct_edges <= 0.25
    assert abs(ours.frac_edges_in_top10pct_vertices - theirs.frac_edges_in_top10pct_vertices) < 0.01
    assert abs(ours.frac_vertices_for_90pct_edges - theirs.frac_vertices_for_90pct_edges) < 0.01


def test_quadrant_shares():
    """The top level's quadrant shares are A/B/C/D: a source id in the upper
    half of the id range needs quadrant C or D (0.24 of edges)."""
    n = 1 << 16
    src, dst = rmat_edges(n, 200_000, seed=7, **GRAPH500)
    assert abs((src >= n // 2).mean() - 0.24) < 0.01
    assert abs((dst >= n // 2).mean() - 0.24) < 0.01
