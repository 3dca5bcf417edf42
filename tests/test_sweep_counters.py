"""The counters and timers that a traced `run_sweep` records as arguments of
its stage spans: each is present where the work runs, the bytes the trace
copies to the host match the figure reckoned from iterations and dtypes,
the timers of a span never add up past its duration, and no span nests
inside a stage span (its self time is what a stage's time reads)."""
from __future__ import annotations

import fnmatch

import pytest

from repro import obs
from repro.core.partition import random_partition
from repro.core.traffic import edge_block_coo
from repro.experiments.cache import SweepCache
from repro.experiments.grid import GridSpec
from repro.experiments.sweep import run_sweep
from repro.graph.generators import rmat

pytest.importorskip("jax")

N, E = 300, 2400
GRID = dict(
    name="counters", workloads=("tiny",), algorithms=("pagerank", "bfs"),
    partitioners=("powerlaw", "random"), placements=("greedy", "random"),
    topologies=("mesh2d",), parts=(4,), contention=True, buffer_depths=(1.0,),
)
# Stages whose self time a benchmark metric reads: nothing may nest in them.
LEAVES = ("sweep.trace", "sweep.partition_traffic", "sweep.placement", "sweep.simulate",
          "nocsim.*.numpy", "nocsim.*.jax")
TIMERS = ("wait_ns", "host_ns", "hash_ns", "partition_ns", "traffic_ns", "read_ns")


def _traced_sweep(cache, **grid):
    tracer = obs.get_tracer()
    tracer.reset()
    obs.enable_tracing()
    try:
        sr = run_sweep(GridSpec(**{**GRID, **grid}), cache=cache, backend="jax",
                       measure_serial=False, graphs={"tiny": rmat(N, E, seed=3)})
    finally:
        obs.disable_tracing()
    spans = tracer.spans()
    tracer.reset()
    return sr, spans


def _one(spans, name):
    (sp,) = [s for s in spans if s.name == name]
    return sp


@pytest.fixture(scope="module")
def sweeps(tmp_path_factory):
    """A cold sweep that fills a cache directory, the warm sweep that reads
    it back, and a sweep with no directory over blocked (sharded) traffic."""
    root = str(tmp_path_factory.mktemp("cache"))
    return {
        "cold": _traced_sweep(SweepCache(root)),
        "warm": _traced_sweep(SweepCache(root)),
        "blocked": _traced_sweep(SweepCache(None), traffic_edge_block=700),
    }


def test_each_argument_is_on_its_span(sweeps):
    for kind in ("cold", "blocked"):
        spans = sweeps[kind][1]
        assert {"wait_ns", "host_ns", "d2h_bytes", "hash_ns"} <= set(_one(spans, "sweep.trace").args)
        assert {"hash_ns", "partition_ns", "traffic_ns"} <= set(_one(spans, "sweep.partition_traffic").args)
    spans = sweeps["warm"][1]
    trace = _one(spans, "sweep.trace").args
    assert {"hash_ns", "read_ns"} <= set(trace) and not {"wait_ns", "d2h_bytes"} & set(trace)
    pt = _one(spans, "sweep.partition_traffic").args
    assert {"hash_ns", "partition_ns", "read_ns"} <= set(pt) and "traffic_ns" not in pt
    for kind in sweeps:
        spans = sweeps[kind][1]
        jax_arms = [s for s in spans if fnmatch.fnmatchcase(s.name, "nocsim.*.jax")]
        # two routings, each open, at depth 1.0 and with unbounded credit
        assert len(jax_arms) == 6
        assert all(s.args["dispatches"] >= 1 for s in jax_arms)
        assert all("dispatches" not in s.args for s in spans if s.name.endswith(".numpy"))
        assert _one(spans, "sweep.records") and _one(spans, "sweep.metrics")


def test_d2h_bytes_match_iterations_and_dtypes(sweeps):
    """Per iteration: the bool edge mask (E), the bool changed mask and the
    float32 change of the N + 1 vertex rows; per trace, the float32 final
    properties of the N vertices."""
    for kind in ("cold", "blocked"):
        sr, spans = sweeps[kind]
        iters = {r.config.algorithm: r.num_iterations for r in sr.records}
        assert set(iters) == {"pagerank", "bfs"} and min(iters.values()) > 1
        want = sum(it * (E + (N + 1) + 4 * (N + 1)) + 4 * N for it in iters.values())
        assert _one(spans, "sweep.trace").args["d2h_bytes"] == want


def test_timers_of_a_span_fit_in_its_duration(sweeps):
    for kind in sweeps:
        for s in sweeps[kind][1]:
            timed = sum(s.args.get(k, 0) for k in TIMERS)
            assert timed <= s.dur_ns, (kind, s.name, s.args, s.dur_ns)


def test_no_span_nests_in_a_stage_span(sweeps):
    for kind in sweeps:
        spans = sweeps[kind][1]
        leaves = [s for s in spans if any(fnmatch.fnmatchcase(s.name, p) for p in LEAVES)]
        assert leaves
        for outer in leaves:
            end = outer.start_ns + outer.dur_ns
            inside = [s.name for s in spans if s is not outer and s.tid == outer.tid
                      and outer.start_ns <= s.start_ns and s.start_ns + s.dur_ns <= end]
            assert inside == [], (kind, outer.name, inside)


def test_blocked_traffic_counts_histogram_blocks(sweeps):
    """Every blocked traffic build reduces its ceil(E / 700) edge blocks and
    its one vertex block through the part-pair histogram (P² = 16 bins, no
    block shorter); the whole-matrix path and cache hits count nothing."""
    pt = _one(sweeps["blocked"][1], "sweep.partition_traffic").args
    assert pt["hist_blocks"] == (-(-E // 700) + 1) * pt["configs"]
    assert pt["configs"] == len(sweeps["blocked"][0].records)
    assert "sort_blocks" not in pt
    for kind in ("cold", "warm"):
        args = _one(sweeps[kind][1], "sweep.partition_traffic").args
        assert not {"hist_blocks", "sort_blocks"} & set(args)


def test_block_counters_record_nothing_while_tracing_is_off():
    g = rmat(N, E, seed=3)
    part = random_partition(g.src, g.dst, N, 4)
    assert not obs.tracing_enabled()
    with obs.span("blocks") as sp:
        edge_block_coo(part, g.src, g.dst, edge_activity=None, packet_bytes=8,
                       model="paper", lo=0, hi=E)
    assert sp.args == {} and obs.get_tracer().spans() == []
