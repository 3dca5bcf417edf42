"""The readers of the counters and timers that the program records as
arguments of its spans: by hand on a synthetic context, and at a tiny size
on the CPU, where each reads a value in exactly the cells that list it."""
import types

import pytest

from bench import common
from bench.tests.cases import benchmark, run_tiny

# metric -> (span it reads, argument, scale)
COUNTERS = {
    "trace_wait_s": ("sweep.trace", "wait_ns", 1e-9),
    "trace_host_s": ("sweep.trace", "host_ns", 1e-9),
    "trace_d2h_mb": ("sweep.trace", "d2h_bytes", 1e-6),
    "cache_hash_s": ("sweep.partition_traffic", "hash_ns", 1e-9),
    "partition_s": ("sweep.partition_traffic", "partition_ns", 1e-9),
    "traffic_s": ("sweep.partition_traffic", "traffic_ns", 1e-9),
    "cache_read_s": ("sweep.trace", "read_ns", 1e-9),
    "nocsim_jax_dispatches": ("nocsim.dor_open.jax", "dispatches", 1.0),
}
NEW = [*COUNTERS, "assemble_s"]


def _span(name, start, dur, tid=1, **args):
    return types.SimpleNamespace(name=name, start_ns=start, dur_ns=dur, tid=tid, args=args)


def _ctx(spans, units=4):
    return types.SimpleNamespace(spans=spans, units=[{}] * units)


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_counter_reader_sums_per_answer(name):
    span, key, scale = COUNTERS[name]
    spans = [_span(span, 0, 10**9, **{key: 3_000_000}), _span(span, 2 * 10**9, 10**9, **{key: 5_000_000}),
             _span(span, 4 * 10**9, 10**9), _span("bench.unit", 0, 6 * 10**9, **{key: 10**12})]
    assert common.reader(name)(_ctx(spans)) == pytest.approx(8_000_000 * scale / 4)
    # the argument absent, or no spans: nothing to read
    assert common.reader(name)(_ctx([_span(span, 0, 10**9)])) is None
    assert common.reader(name)(_ctx(None)) is None


def test_cache_hash_and_read_sum_both_stages():
    for name, key in (("cache_hash_s", "hash_ns"), ("cache_read_s", "read_ns")):
        spans = [_span("sweep.trace", 0, 10**9, **{key: 10**9}),
                 _span("sweep.partition_traffic", 2 * 10**9, 10**9, **{key: 3 * 10**9})]
        assert common.reader(name)(_ctx(spans, units=2)) == pytest.approx(2.0)


def test_assemble_reads_the_self_time_of_records_and_metrics():
    spans = [_span("sweep.records", 0, 3 * 10**9), _span("sweep.metrics", 5 * 10**9, 10**9),
             _span("inner", 1 * 10**9, 10**9)]
    assert common.reader("assemble_s")(_ctx(spans, units=2)) == pytest.approx((2 + 1) / 2)
    assert common.reader("assemble_s")(_ctx([_span("sweep.trace", 0, 10**9)])) is None


def test_workloads_are_the_cells_where_each_reader_reads(monkeypatch):
    """Every per-layer reader runs on every cell at a tiny size; each new
    metric's `workloads` is exactly the cells where it read a value."""
    bm = benchmark()
    monkeypatch.setattr(common, "metrics_of", lambda bm, cell, kind: bm[kind])
    read = {c["name"]: set(run_tiny(c["name"], seconds=0.1, trace=True)["metrics"])
            for c in bm["workloads"]}
    for entry in bm["per_layer"]:
        if entry["name"] in NEW:
            assert set(entry["workloads"]) == {c for c, names in read.items() if entry["name"] in names}, entry
