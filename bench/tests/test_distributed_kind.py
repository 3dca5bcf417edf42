"""The distributed kind at a size the CPU holds, on four host devices: a
correct run, every reader of its metrics, the limits covering every
number its check returns, a planted fault and the bfloat16 control, both
of which the check catches."""
import types

import jax
import pytest

from bench import common
from bench import control_distributed as control
from bench import tracereduce as tr
from bench.tests.cases import SEED, TINY, benchmark, devices_for

# The distributed cell runs one engine a device, on four.  The CPU backend
# gives as many devices as it is asked for before it starts, and it starts
# with the first test of the session, after every test file is collected:
# this asks for four for every cell's test at a tiny size.
jax.config.update("jax_num_cpu_devices", 4)

CELL = "pokec.distributed"
NEW_METRICS = ("dist_run_s", "dist_wait_s", "dist_exchange_mb", "collective_share.distributed",
               "dist_step_roofline", "device_idle_share.distributed")


def _run(seed=SEED, *, trace=False, prepare=None):
    from bench.harness import run_cell

    return run_cell(CELL, seed, 0.2, trace, devices_for(CELL), bm=benchmark(), cfg=dict(TINY),
                    prepare=prepare)


def test_runs_correct_and_reads_the_span_metrics():
    kept = {}
    limits = common.limits(CELL)
    traced = _run(trace=True, prepare=lambda w: kept.setdefault("work", w))
    plain = _run(SEED + 1)
    for result in (traced, plain):
        assert result["correct"], result["checks"]
        assert result["failed"] == 0 and result["attempted"] >= 1
        assert set(result["checks"]) == set(limits)
        assert result["device"]["count"] == 4
    assert set(plain["metrics"]) == {"setup_s", "answer_s"}
    # on the CPU the trace has no TPU planes: the span readers report, the
    # device readers stay silent
    metrics = traced["metrics"]
    assert set(metrics) == {"dist_run_s", "dist_wait_s", "dist_exchange_mb"}
    info, units = kept["work"].info, traced["attempted"]
    steps = sum(sum(u.values()) for u in info["iterations"][-units:])
    # the bytes are exact; only their scaling to MB per unit rounds
    assert metrics["dist_exchange_mb"]["value"] == pytest.approx(steps * 4 * info["n_local"] * 4 / 1e6 / units,
                                                                 rel=1e-12)
    assert 0 < metrics["dist_wait_s"]["value"] < metrics["dist_run_s"]["value"]


def test_a_bfs_root_off_by_one_is_not_correct(monkeypatch):
    from repro.graph.distributed import DistributedEngine

    real = DistributedEngine.run

    def off_by_one(self, sg, *, source=0, **kw):
        if self.program.name == "bfs":
            source = (source + 1) % sg.num_nodes
        return real(self, sg, source=source, **kw)

    monkeypatch.setattr(DistributedEngine, "run", off_by_one)
    result = _run()
    assert not result["correct"]
    assert result["checks"]["bfs_mismatch"]["value"] > 0


def test_the_bfloat16_control_is_not_correct():
    """The bfloat16 exchange and the reference in bfloat16 both break
    PageRank's limit; BFS levels stay exact."""
    kept = {}

    def prepare(work):
        kept["work"] = work
        work.comm_dtype = "bfloat16"

    result = _run(prepare=prepare)
    checks = result["checks"]
    assert not result["correct"]
    assert checks["pagerank_rel"]["value"] > checks["pagerank_rel"]["limit"]
    assert checks["bfs_mismatch"]["value"] == 0
    assert control.bf16_readings(kept["work"])["pagerank_rel"] > checks["pagerank_rel"]["limit"]


def _info(iterations):
    return {"num_nodes": 100, "num_edges": 1000, "engines": 2, "n_local": 50, "e_local": 500,
            "exchange_itemsize": 4, "iterations": iterations}


def test_device_readers_on_a_trace_by_hand():
    """Two chips, one unit of one BFS step and one PageRank step in the
    window [1000, 2000): each chip runs two `dist_step` programs, and
    within them an all-to-all and an all-reduce."""
    # operations as the profiler names them on a TPU: the instruction's HLO
    # text; the fusion that reads the exchanged block is no collective
    a2a = "%all_to_all.7 = f32[2,1,50]{2,1,0} all-to-all(%all_to_all.6), channel_id=1, replica_groups={{0,1}}"
    psum_start = "%psum.7 = (f32[], f32[]) all-reduce-start(%fusion.8), channel_id=2"
    psum_done = "%psum.8 = f32[] all-reduce-done(%psum.7)"
    fold = "%fusion.9 = f32[51]{0} fusion(%all_to_all.7, %psum.8), kind=kLoop, calls=%fused_computation.9"
    devices = {}
    for chip in (0, 1):
        devices[chip] = {
            "ops": [(fold, 1000, 1100), (a2a, 1100, 1150), (psum_start, 1150, 1160),
                    (psum_done, 1160, 1200), (fold, 1500, 1600), (a2a, 1600, 1700)],
            "modules": [("jit_dist_step(1)", 1000, 1200), ("jit_dist_step(1)", 1500, 1700),
                        ("jit_any(2)", 1800, 1810)],
        }
    red = tr.Reduction(devices, 1000, 2000, [], 0)
    ctx = types.SimpleNamespace(device=red, mix={"kind": "distributed"}, units=[{}],
                                info=_info([{"bfs": 3, "pagerank": 2}, {"bfs": 1, "pagerank": 1}]),
                                peaks={"hbm_bytes_per_s": 819e9}, spans=[])
    read = {name: common.reader(name) for name in NEW_METRICS}
    assert abs(read["collective_share.distributed"](ctx) - 100.0 * 200 / 400) < 1e-9
    assert abs(read["device_idle_share.distributed"](ctx) - 100.0 * (1 - 400 / 1000)) < 1e-9
    from bench.kernels_distributed import dist_step_bytes

    least = dist_step_bytes(50, 500, 2, False) + dist_step_bytes(50, 500, 2, True)
    want = 100.0 * least / 819e9 / 400e-9
    assert abs(read["dist_step_roofline"](ctx) - want) < 1e-9 * want
    # the trace must hold every step of the window's units, on every chip
    ctx.info = _info([{"bfs": 2, "pagerank": 2}])
    with pytest.raises(RuntimeError):
        read["dist_step_roofline"](ctx)
    # outside a distributed cell, or untraced, every reader is silent
    for name in NEW_METRICS:
        assert read[name](types.SimpleNamespace(device=None, mix={"kind": "sweep"}, spans=None, units=[])) is None
