"""Each cell's unit at a tiny size on the CPU, through the function the CLI
calls, with the cell's own mix, limits and metrics."""
import pytest

from bench import common
from bench.tests.cases import benchmark, run_tiny

CELLS = [c["name"] for c in benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_a_tiny_size(cell):
    result = run_tiny(cell, seconds=2.0)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    want = {m["name"] for m in common.metrics_of(benchmark(), cell, "end_to_end")}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert result["device"]["count"] == common.cell(benchmark(), cell)["chips"]


def test_traced_sweep_reads_the_span_metrics():
    result = run_tiny("pokec.front", trace=True)
    assert result["correct"]
    for name in ("trace_s", "partition_traffic_s", "placement_s", "simulate_s",
                 "nocsim_jax_s", "nocsim_numpy_s"):
        assert result["metrics"][name]["value"] > 0
    # the CPU has no device plane: the device metrics find nothing to read
    assert "device_idle_share.sweep" not in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
