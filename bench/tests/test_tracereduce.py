"""The reduction from a profiler trace to busy time, idle share, time by
program and labelled idle gaps."""
import types

from bench import tracereduce as tr


def _span(name, start, dur, tid=1):
    return types.SimpleNamespace(name=name, start_ns=start, dur_ns=dur, tid=tid)


def test_union_and_gaps():
    iv = [(10, 20), (15, 30), (40, 50), (45, 46), (90, 200)]
    assert tr.union_ns(iv, 0, 100) == 20 + 10 + 10
    assert tr.gaps(iv, 0, 100) == [(0, 10), (30, 40), (50, 90)]
    assert tr.union_ns([], 0, 100) == 0 and tr.gaps([], 0, 100) == [(0, 100)]


def test_module_name_drops_the_run_id():
    assert tr.module_name("jit__one_iteration(42)") == "jit__one_iteration"
    assert tr.module_name("jit_kernel") == "jit_kernel"


def test_reduction_by_hand():
    # two devices; the window is [1000, 2000) on the trace clock; obs time is
    # trace time - 500
    devices = {
        0: {"ops": [("fusion.1", 1000, 1200), ("all-to-all.3", 1200, 1300), ("fusion.2", 1600, 1700)],
            "modules": [("jit_step(1)", 1000, 1300), ("jit_other(2)", 1600, 1700)]},
        1: {"ops": [("fusion.1", 1000, 1100)], "modules": [("jit_step(1)", 1000, 1100)]},
    }
    spans = [_span("bench.unit", 500, 1000), _span("sweep.trace", 800, 300), _span("sweep.nocsim", 1250, 200)]
    red = tr.Reduction(devices, 1000, 2000, spans, offset_ns=500)
    assert red.window_s == 1e-6
    assert abs(red.busy_s - (400 + 100) / 2 / 1e9) < 1e-18
    assert abs(red.idle_share() - 0.75) < 1e-12
    assert abs(red.module_time_s(lambda n: n == "jit_step") - (300 + 100) / 2 / 1e9) < 1e-18
    assert abs(red.op_time_s(lambda n: "all-to-all" in n) - 100 / 2 / 1e9) < 1e-18
    assert red.module_runs(lambda n: n == "jit_step") == 2
    b = red.breakdown()
    assert b["device_ops"][0][0] == "jit_step"
    # device 0 idles over [1300, 1600) (middle 1450 -> obs 950: bench.unit;
    # sweep.trace covers obs [800, 1100) and is shorter) and [1700, 2000)
    # (middle 1850 -> obs 1350: sweep.nocsim)
    labels = dict(b["idle_gaps"])
    assert abs(labels["sweep.trace"] - 300e-9) < 1e-18
    assert abs(labels["sweep.nocsim"] - 300e-9) < 1e-18


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: four `traced_step` runs of a tiny BFS
    inside a window that ends with a 50 ms host sleep in its own span."""
    import json
    import os

    here = os.path.join(os.path.dirname(__file__), "fixtures")
    with open(os.path.join(here, "tpu_bfs.json")) as fh:
        meta = json.load(fh)
    spans = [_span(n, a, d, t) for n, a, d, t in meta["spans"]]
    red = tr.reduce(os.path.join(here, "tpu_bfs.xplane.pb"), meta["sync_ns"], meta["t0_ns"], meta["t1_ns"], spans)
    assert list(red.devices) == [0]
    assert abs(red.window_s - (meta["t1_ns"] - meta["t0_ns"]) / 1e9) < 1e-12
    assert 0 < red.busy_s < red.window_s
    assert red.module_runs(lambda n: "_one_iteration" in n) == meta["traced_step_runs"]
    assert 0 < red.module_time_s(lambda n: "_one_iteration" in n) <= red.busy_s
    idle = dict(red.breakdown()["idle_gaps"])
    # the device idles through the host's sleep, and the reduction says so
    assert 0.045 < idle["fixture.host_wait"] < 0.06
    assert red.breakdown()["device_ops"][0][0] == "jit__one_iteration"
