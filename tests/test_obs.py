"""Observability layer (`repro.obs`): span tracer determinism, Chrome-trace
schema conformance, metrics-registry namespaces, flight-recorder ring-buffer
accounting, and the load-bearing integration contract — turning recording on
leaves every sweep artifact byte-identical (RPL005) and never touches the
jax carry (RPL001: the recorder only ever sees the numpy reference arm).
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import obs
from repro.core.noc import Mesh2D
from repro.core.placement import random_placement
from repro.core.traffic import TrafficMatrix
from repro.nocsim import NocSimParams, contended_batch
from repro.obs import FlightRecorder, Span, Tracer, metrics, span
from repro.obs.validate import validate, validate_file

REPO = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(REPO, "src")
TRACE_SCHEMA = os.path.join(REPO, "schemas", "trace.schema.json")
METRICS_SCHEMA = os.path.join(REPO, "schemas", "metrics.schema.json")


def _load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _read_bytes(path):
    with open(path, "rb") as fh:
        return fh.read()


@pytest.fixture
def clean_tracer():
    """The module singleton is process-global state; leave it as found."""
    tracer = obs.get_tracer()
    tracer.reset()
    obs.disable_tracing()
    yield tracer
    tracer.reset()
    obs.disable_tracing()


def _random_traffic(parts: int, seed: int, density: float = 0.4) -> TrafficMatrix:
    rng = np.random.default_rng(seed)
    n = 4 * parts
    m = rng.random((n, n)) * (rng.random((n, n)) < density) * 1000.0
    np.fill_diagonal(m, 0.0)
    return TrafficMatrix(
        num_parts=parts,
        bytes_matrix=m,
        phase_bytes={"process": float(m.sum()), "reduce": 0.0, "apply": 0.0},
    )


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------


class TestSpanTracer:
    def test_span_measures_even_when_tracing_disabled(self, clean_tracer):
        with span("work", cat="test") as sp:
            pass
        assert sp.duration_s >= 0.0
        assert clean_tracer.spans() == []  # nothing buffered while disabled

    def test_exception_annotates_error_and_propagates(self, clean_tracer):
        obs.enable_tracing()
        with pytest.raises(ValueError):
            with span("doomed", cat="test"):
                raise ValueError("boom")
        (sp,) = clean_tracer.spans()
        assert sp.args["error"] == "ValueError"

    def test_annotate_after_exit_reaches_buffered_span(self, clean_tracer):
        # resilience.py annotates unit spans after the `with` block closes;
        # the buffer holds the span by reference, so that must stick.
        obs.enable_tracing()
        with span("faults.unit", cat="faults") as sp:
            pass
        sp.annotate(num_dead_links=3)
        (buffered,) = clean_tracer.spans()
        assert buffered.args["num_dead_links"] == 3

    def test_nesting_and_ordering_deterministic_under_seeded_concurrency(
        self, clean_tracer
    ):
        """4 threads racing through identical nested structure: export
        groups spans by tid, and WITHIN each thread track the order is a
        pure function of the code path — outer first, children in program
        order, child intervals contained in the parent's."""
        obs.enable_tracing()
        n_workers, n_inner = 4, 3
        barrier = threading.Barrier(n_workers)

        def worker(i):
            barrier.wait()  # maximize interleaving
            with span(f"w{i}.outer", cat="test"):
                for j in range(n_inner):
                    with span(f"w{i}.s{j}", cat="test"):
                        pass

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        x_events = [e for e in clean_tracer.to_events() if e["ph"] == "X"]
        by_tid: dict[int, list[dict]] = {}
        for e in x_events:
            by_tid.setdefault(e["tid"], []).append(e)
        assert len(by_tid) == n_workers  # one Chrome-trace track per thread

        seen_sequences = set()
        for events in by_tid.values():
            names = [e["name"] for e in events]
            i = int(names[0].split(".")[0][1:])
            assert names == [f"w{i}.outer"] + [f"w{i}.s{j}" for j in range(n_inner)]
            outer, inner = events[0], events[1:]
            for e in inner:  # parent interval contains every child
                assert outer["ts"] <= e["ts"]
                assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-6
            seen_sequences.add(tuple(names))
        assert len(seen_sequences) == n_workers  # each worker on its own track

    def test_export_order_is_sorted_and_stable(self, clean_tracer):
        obs.enable_tracing()
        for name in ("b", "a", "c"):
            with span(name, cat="test"):
                pass
        events = clean_tracer.to_events()
        x = [e for e in events if e["ph"] == "X"]
        # insertion order was b, a, c; export sorts by start time
        starts = [e["ts"] for e in x]
        assert starts == sorted(starts)
        assert [e["name"] for e in x] == ["b", "a", "c"]

    def test_buffer_truncation_is_counted_never_silent(self, tmp_path):
        tracer = Tracer(max_spans=2)
        tracer.enabled = True
        for i in range(5):
            s = Span(f"s{i}", cat="test")
            s.start_ns, s.dur_ns = i * 10, 5
            s.pid, s.tid = os.getpid(), 1
            tracer.add(s)
        assert len(tracer.spans()) == 2
        assert tracer.dropped_spans == 3
        tracer.export(str(tmp_path / "t.json"))
        payload = _load(tmp_path / "t.json")
        assert payload["otherData"]["dropped_spans"] == 3

    def test_numpy_args_are_coerced_to_json(self, clean_tracer, tmp_path):
        obs.enable_tracing()
        with span("np", cat="test", value=np.float64(1.5), count=np.int32(4)):
            pass
        clean_tracer.export(str(tmp_path / "t.json"))
        payload = _load(tmp_path / "t.json")  # file round-trips
        (x,) = [e for e in payload["traceEvents"] if e["ph"] == "X"]
        assert x["args"] == {"count": 4.0, "value": 1.5}

    def test_deterministic_clock_mode_in_fresh_process(self):
        """REPRO_OBS_DETERMINISTIC=1 (read at import): clock ticks one fixed
        quantum per read and peak_rss_mb reports 0 — timing becomes a pure
        function of clock-read count."""
        body = (
            "from repro import obs\n"
            "assert obs.deterministic_clock_active()\n"
            "a, b = obs.now_ns(), obs.now_ns()\n"
            "assert (a, b) == (1000, 2000), (a, b)\n"
            "assert obs.peak_rss_mb() == 0.0\n"
            "with obs.span('x') as sp:\n"
            "    pass\n"
            "assert sp.dur_ns == 1000\n"
        )
        env = dict(os.environ, PYTHONPATH=SRC, REPRO_OBS_DETERMINISTIC="1")
        subprocess.run([sys.executable, "-c", body], env=env, check=True, timeout=120)


# ---------------------------------------------------------------------------
# Counters and timers (span arguments)
# ---------------------------------------------------------------------------


class TestCountersAndTimers:
    def test_count_and_timer_land_on_the_innermost_open_span(self, clean_tracer):
        obs.enable_tracing()
        with span("outer", cat="test") as outer:
            obs.count("items", 2)
            with span("inner", cat="test") as inner:
                obs.count("items", 3)
                obs.count("items")
                with obs.timer("work_ns"):
                    pass
            obs.count("items", 5)
            with obs.timer("work_ns"):
                pass
            with obs.timer("work_ns"):
                pass
        assert inner.args["items"] == 4 and outer.args["items"] == 7
        assert inner.args["work_ns"] >= 0 and outer.args["work_ns"] >= 0
        assert inner.args["work_ns"] + outer.args["work_ns"] <= outer.dur_ns
        obs.count("items", 100)  # no open span: written nowhere
        with obs.timer("work_ns"):
            pass
        assert outer.args["items"] == 7

    def test_each_thread_writes_to_its_own_span(self, clean_tracer):
        obs.enable_tracing()
        n_workers, n_counts = 4, 50
        barrier = threading.Barrier(n_workers)
        spans = {}

        def worker(i):
            with span(f"w{i}", cat="test") as sp:
                spans[i] = sp
                barrier.wait()  # every thread's span open at once
                for _ in range(n_counts):
                    obs.count("n", i + 1)
                    with obs.timer("t_ns"):
                        pass

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, sp in spans.items():
            assert sp.args["n"] == n_counts * (i + 1)
            assert 0 <= sp.args["t_ns"] <= sp.dur_ns

    def test_nothing_is_written_with_tracing_off(self, clean_tracer):
        with span("quiet", cat="test") as sp:
            obs.count("items", 3)
            with obs.timer("work_ns"):
                pass
        assert sp.args == {}
        assert clean_tracer.spans() == []

    def test_timer_that_outlives_its_span_writes_nowhere(self, clean_tracer):
        obs.enable_tracing()
        with span("outer", cat="test") as outer:
            t = obs.timer("work_ns")
            with span("inner", cat="test") as inner:
                t.__enter__()
            t.__exit__(None, None, None)  # its span has closed
            obs.count("after", 1)
        assert "work_ns" not in inner.args and "work_ns" not in outer.args
        assert outer.args["after"] == 1

    def test_span_left_open_above_an_exiting_span_is_dropped(self, clean_tracer):
        obs.enable_tracing()
        with span("outer", cat="test") as outer:
            span("leaked", cat="test").__enter__()
        with span("next", cat="test") as nxt:
            obs.count("items")
        assert nxt.args == {"items": 1} and "items" not in outer.args

    def test_clock_reads_do_not_depend_on_tracing(self):
        """Under REPRO_OBS_DETERMINISTIC=1 a timer reads the clock twice
        and a count not at all, tracing on or off, so payload timings stay
        byte-identical when tracing is turned on."""
        body = (
            "from repro import obs\n"
            "def reads():\n"
            "    a = obs.now_ns()\n"
            "    with obs.span('s'):\n"
            "        obs.count('n', 1)\n"
            "        with obs.timer('t_ns'):\n"
            "            obs.count('n', 2)\n"
            "    return (obs.now_ns() - a) // 1000\n"
            "off = reads()\n"
            "obs.enable_tracing()\n"
            "on = reads()\n"
            "(sp,) = obs.get_tracer().spans()\n"
            "assert sp.args == {'n': 3, 't_ns': 1000}, sp.args\n"
            "assert off == on == 5, (off, on)\n"
        )
        env = dict(os.environ, PYTHONPATH=SRC, REPRO_OBS_DETERMINISTIC="1")
        subprocess.run([sys.executable, "-c", body], env=env, check=True, timeout=120)


# ---------------------------------------------------------------------------
# Chrome-trace schema
# ---------------------------------------------------------------------------


class TestTraceSchema:
    def test_exported_trace_validates_against_checked_in_schema(
        self, clean_tracer, tmp_path
    ):
        obs.enable_tracing()
        with span("sweep.trace", cat="sweep", grid="mini"):
            with span("inner", cat="sweep"):
                pass
        rec = FlightRecorder(max_windows=4)
        rec.capture_batch(*_tiny_batch(windows=3))
        path = str(tmp_path / "trace.json")
        clean_tracer.export(path, extra_events=rec.to_counter_events())
        assert validate_file(path, TRACE_SCHEMA) == []

    def test_validator_rejects_malformed_trace(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"traceEvents": {"not": "a list"}}))
        errors = validate_file(str(path), TRACE_SCHEMA)
        assert errors  # the validator has teeth

    def test_validator_core_combinators(self):
        schema = {
            "type": "object",
            "required": ["ph"],
            "properties": {"ph": {"enum": ["X", "C", "M"]}, "ts": {"type": "number", "minimum": 0}},
        }
        assert validate({"ph": "X", "ts": 1.0}, schema) == []
        assert validate({"ph": "Z"}, schema)
        assert validate({"ph": "X", "ts": -1}, schema)
        assert validate({}, schema)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = metrics.MetricsRegistry()
        reg.counter("c", non_comparable=True).inc(2, kind="hit")
        reg.counter("c", non_comparable=True).inc(1, kind="hit")
        reg.gauge("g").set(3.5, stage="trace")
        h = reg.histogram("h", non_comparable=True)
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        snap = reg.snapshot()
        assert metrics.series_value(snap, "c", kind="hit") == 3
        assert metrics.series_value(snap, "g", stage="trace") == 3.5
        hv = metrics.series_value(snap, "h")
        assert (hv["count"], hv["sum"], hv["min"], hv["max"]) == (3, 6.0, 1.0, 3.0)

    def test_kind_mismatch_raises(self):
        reg = metrics.MetricsRegistry()
        reg.counter("m")
        with pytest.raises(ValueError, match="already registered as counter"):
            reg.gauge("m")
        with pytest.raises(ValueError, match="not a counter"):
            reg.gauge("g").inc(1)

    def test_namespace_mismatch_raises(self):
        # the comparable/non_comparable split is part of the metric's
        # identity — silently flipping it would corrupt the contract
        reg = metrics.MetricsRegistry()
        reg.counter("m", non_comparable=True)
        with pytest.raises(ValueError, match="non_comparable"):
            reg.counter("m", non_comparable=False)

    def test_snapshot_namespace_split(self):
        reg = metrics.MetricsRegistry()
        reg.gauge("placement.stats").set(7, stat="iterations")
        reg.counter("cache.events", non_comparable=True).inc(1, kind="trace_hits")
        snap = reg.snapshot()
        assert set(snap["comparable"]) == {"placement.stats"}
        assert set(snap["non_comparable"]) == {"cache.events"}
        assert snap["version"] == 1

    def test_histogram_reservoir_is_bounded(self):
        reg = metrics.MetricsRegistry()
        h = reg.histogram("h")
        for v in range(300):
            h.observe(float(v))
        hv = metrics.series_value(reg.snapshot(), "h")
        assert hv["count"] == 300
        assert len(hv["samples"]) == 256  # bounded; count keeps the truth

    def test_series_map_flattens_by_label(self):
        reg = metrics.MetricsRegistry()
        g = reg.gauge("sweep.stage_seconds", non_comparable=True)
        g.set(1.0, grid="mini", stage="trace")
        g.set(2.0, grid="mini", stage="placement")
        m = metrics.series_map(reg.snapshot(), "sweep.stage_seconds", "stage")
        assert m == {"trace": 1.0, "placement": 2.0}

    def test_snapshot_file_validates_against_checked_in_schema(self, tmp_path):
        reg = metrics.MetricsRegistry()
        reg.gauge("nocsim.saturation_bytes_per_s").set(1e9, key="k", routing="dor")
        reg.histogram("train.step_ms", non_comparable=True).observe(2.0)
        path = str(tmp_path / "metrics.json")
        reg.write_snapshot(path)
        assert validate_file(path, METRICS_SCHEMA) == []


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class _StubSchedule:
    """Just the attributes `capture_batch` reads off a ConfigSchedule."""

    def __init__(self, num_links: int, num_windows: int, window_s: float = 1e-6):
        self.window_s = window_s
        self.num_links = num_links
        share = np.zeros((num_windows, 3))
        share[:, 0] = 1.0  # every window in the "process" phase
        self.window_share = share


def _tiny_batch(windows: int = 3, links: int = 2, configs: int = 1):
    scheds = [_StubSchedule(links, windows) for _ in range(configs)]
    serviced = np.linspace(0.0, 1.0, windows * configs * links).reshape(
        windows, configs, links
    )
    backlog = serviced * 0.5
    return scheds, serviced, backlog


class TestFlightRecorder:
    def test_ring_truncation_accounting_exact(self):
        """32 windows into an 8-deep ring, fed in 4-window chunks (the
        run_windows cadence): 24 dropped, last 8 retained, and the drop
        count surfaces in summary(), the heatmap, AND the Perfetto
        process_labels — never silent."""
        rec = FlightRecorder(max_windows=8)
        total, chunk = 32, 4
        for start in range(0, total, chunk):
            scheds, serviced, backlog = _tiny_batch(windows=chunk)
            # window_share is per-chunk in the stub; absolute phase lookup
            # falls back to PHASES[0] past its end, which is fine here
            rec.capture_batch(scheds, serviced, backlog, start_window=start)
        assert rec.dropped_windows == total - 8
        (track,) = rec.summary()["tracks"]
        assert track["windows_retained"] == 8
        assert track["windows_dropped"] == 24
        events = rec.to_counter_events()
        (labels,) = [e for e in events if e["name"] == "process_labels"]
        assert "dropped=24" in labels["args"]["labels"]
        heat = rec.phase_heatmap()
        assert heat["tracks"][0]["windows_dropped"] == 24
        # retained counters are the LAST 8 windows (ring evicts oldest)
        c_ts = sorted({e["ts"] for e in events if e["ph"] == "C"})
        window_us = 1e-6 * 1e6
        assert c_ts == [w * window_us for w in range(24, 32)]

    def test_counter_track_shape_and_naming(self):
        rec = FlightRecorder(max_windows=16)
        scheds, serviced, backlog = _tiny_batch(windows=3, links=2, configs=2)
        rec.capture_batch(scheds, serviced, backlog, arm="dor", keys=["cfgA", "cfgB"])
        events = rec.to_counter_events(pid_base=500)
        names = [e["args"]["name"] for e in events if e["name"] == "process_name"]
        assert names == ["noc cfgA [dor]", "noc cfgB [dor]"]
        c = [e for e in events if e["ph"] == "C"]
        assert len(c) == 2 * 3 * 2  # configs × windows × links
        assert {e["name"] for e in c} == {"link00", "link01"}
        assert all(set(e["args"]) == {"util", "backlog"} for e in c)
        assert {e["pid"] for e in c} == {500, 501}

    def test_counter_events_json_matches_dict_path(self):
        """The pre-serialized fast path is the same event stream as
        `to_counter_events`, event for event (values through `%g`)."""
        rec = FlightRecorder(max_windows=16)
        scheds, serviced, backlog = _tiny_batch(windows=3, links=2, configs=2)
        rec.capture_batch(scheds, serviced, backlog, arm="dor", keys=["a", "b"])
        dicts = rec.to_counter_events()
        parsed = [json.loads(s) for s in rec.counter_events_json()]
        assert len(parsed) == len(dicts)
        for d, p in zip(dicts, parsed):
            assert set(p) == set(d)
            for k in ("ph", "name", "pid", "tid"):
                if k in d:
                    assert p[k] == d[k]
            if d["ph"] == "C":
                assert p["ts"] == pytest.approx(d["ts"], rel=1e-5, abs=1e-9)
                for series in ("util", "backlog"):
                    assert p["args"][series] == pytest.approx(
                        d["args"][series], rel=1e-5, abs=1e-9
                    )
            else:
                assert p["args"] == d["args"]

    def test_phase_heatmap_means(self):
        rec = FlightRecorder(max_windows=16)
        scheds = [_StubSchedule(1, 4)]
        serviced = np.array([[[0.2]], [[0.4]], [[0.6]], [[0.8]]])
        rec.capture_batch(scheds, serviced, serviced * 0.0)
        heat = rec.phase_heatmap()
        (track,) = heat["tracks"]
        assert track["window_counts"]["process"] == 4
        assert track["mean_util"]["process"][0] == pytest.approx(0.5)
        assert track["mean_util"]["reduce"] == []  # no windows in that phase

    def test_max_windows_validated(self):
        with pytest.raises(ValueError):
            FlightRecorder(max_windows=0)


# ---------------------------------------------------------------------------
# NocSim integration: the recorder must be invisible to results & payloads
# ---------------------------------------------------------------------------


class TestNocSimRecorderIntegration:
    def test_recorder_invisible_to_asdict_replace_eq(self):
        rec = FlightRecorder()
        p_rec = NocSimParams(record_timeline=rec)
        p_plain = NocSimParams()
        assert p_rec == p_plain  # InitVar: not a field, not part of identity
        d = dataclasses.asdict(p_rec)
        assert "recorder" not in d and "record_timeline" not in d
        assert d == dataclasses.asdict(p_plain)  # payload sites unperturbed
        assert p_rec.recorder is rec
        assert dataclasses.replace(p_rec, inj_rate=2.0).recorder is None

    def test_recording_on_equals_recording_off(self):
        """The load-bearing contract: attaching a recorder changes NOTHING
        about simulation results — it reads timelines the run already
        produced at chunk boundaries."""
        traffics, placements = [], []
        for seed in (0, 1):
            t = _random_traffic(4, seed)
            traffics.append(t)
            placements.append(random_placement(t.num_logical, Mesh2D(4, 4), seed=seed))
        rec = FlightRecorder(max_windows=64)
        p_rec = NocSimParams(profile="phases", record_timeline=rec)
        p_off = NocSimParams(profile="phases")
        r_rec = contended_batch(
            traffics, placements, noc_params=p_rec, backend="numpy",
            window_chunk=8, config_keys=["a", "b"],
        )
        r_off = contended_batch(traffics, placements, noc_params=p_off, backend="numpy")
        for a, b in zip(r_rec, r_off):
            assert a.to_dict() == b.to_dict()
        summ = rec.summary()
        assert {t["key"] for t in summ["tracks"]} == {"a", "b"}
        assert all(t["windows_retained"] > 0 for t in summ["tracks"])

    def test_credit_arm_records_labeled_track(self):
        t = _random_traffic(4, 3)
        pl = random_placement(t.num_logical, Mesh2D(4, 4), seed=3)
        rec = FlightRecorder(max_windows=64)
        params = NocSimParams(
            flow_control="credit", buffer_depth=4.0, record_timeline=rec
        )
        r_rec = contended_batch([t], [pl], noc_params=params, backend="numpy")
        r_off = contended_batch(
            [t], [pl],
            noc_params=NocSimParams(flow_control="credit", buffer_depth=4.0),
            backend="numpy",
        )
        assert r_rec[0].to_dict() == r_off[0].to_dict()
        (track,) = rec.summary()["tracks"]
        assert track["arm"] == "dor+credit(d=4)"
        assert track["windows_retained"] > 0

    def test_jax_backend_never_feeds_recorder(self):
        pytest.importorskip("jax")
        t = _random_traffic(4, 5)
        pl = random_placement(t.num_logical, Mesh2D(4, 4), seed=5)
        rec = FlightRecorder()
        params = NocSimParams(record_timeline=rec)
        contended_batch([t], [pl], noc_params=params, backend="jax")
        # RPL001: recording hooks the numpy reference arm only — nothing
        # may tap the lax.scan carry
        assert rec.summary()["tracks"] == []


# ---------------------------------------------------------------------------
# Pipeline byte-identity (subprocess, deterministic clock)
# ---------------------------------------------------------------------------


def _run_grid(workdir, grid, extra=(), metrics_out=None, trace_out=None):
    os.makedirs(workdir, exist_ok=True)
    cmd = [
        sys.executable, "-m", "repro.experiments.run",
        "--grid", grid, "--backend", "numpy",
        "--cache-dir", os.path.join(workdir, "cache"),
        "--md", os.path.join(workdir, "EXP.md"),
        "--json", os.path.join(workdir, "BENCH.json"),
        "-q", *extra,
    ]
    if trace_out:
        cmd += ["--trace-out", trace_out]
    if metrics_out:
        cmd += ["--metrics-out", metrics_out]
    env = dict(os.environ, PYTHONPATH=SRC, REPRO_OBS_DETERMINISTIC="1")
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return out


class TestPipelineByteIdentity:
    def test_tracing_on_vs_off_identical_mini_artifacts(self, tmp_path):
        """ISSUE acceptance: mini grid with --trace-out/--metrics-out vs
        without — EXPERIMENTS.md and BENCH_sweep.json byte-identical, and
        the trace is valid Chrome-trace JSON with pipeline spans and at
        least one per-link counter track."""
        a, b = str(tmp_path / "off"), str(tmp_path / "on")
        trace = os.path.join(b, "trace.json")
        mets = os.path.join(b, "metrics.json")
        _run_grid(a, "mini")
        _run_grid(b, "mini", trace_out=trace, metrics_out=mets)

        for name in ("EXP.md", "BENCH.json"):
            assert _read_bytes(os.path.join(a, name)) == _read_bytes(
                os.path.join(b, name)
            ), f"{name} differs with tracing on"

        assert validate_file(trace, TRACE_SCHEMA) == []
        payload = _load(trace)
        names = {e["name"] for e in payload["traceEvents"] if e["ph"] == "X"}
        assert "pipeline.sweep" in names
        assert {"sweep.trace", "sweep.placement", "sweep.simulate"} <= names
        counters = [e for e in payload["traceEvents"] if e["ph"] == "C"]
        assert counters and counters[0]["name"].startswith("link")
        assert payload["otherData"]["deterministic_clock"] is True

        assert validate_file(mets, METRICS_SCHEMA) == []
        snap = _load(mets)
        stages = metrics.series_map(snap, "sweep.stage_seconds", "stage")
        assert "placement" in stages
        # mini runs no contention arm, so the comparable namespace carries
        # the placement descent stats (saturation bounds appear on grids
        # with contention records)
        assert "placement.stats" in snap["comparable"]

        heat_path = os.path.splitext(trace)[0] + ".heatmap.json"
        heat = _load(heat_path)
        assert heat["tracks"] and all("mean_util" in t for t in heat["tracks"])

    def test_resume_with_metrics_keeps_faults_artifact_identical(self, tmp_path):
        """Satellite 2: the comparable namespace is resume-invariant and the
        faults artifact stays byte-identical; resume-dependence lives ONLY
        in non_comparable (resumed vs computed unit counts)."""
        wd = str(tmp_path)
        sweeps = os.path.join(wd, "sweeps")
        journal = os.path.join(wd, "journal.json")
        m1, m2 = os.path.join(wd, "m1.json"), os.path.join(wd, "m2.json")
        extra = ["--sweeps-dir", sweeps, "--journal", journal]
        _run_grid(wd, "minifaults", extra=extra, metrics_out=m1)
        artifact = os.path.join(sweeps, "minifaults.json")
        first = _read_bytes(artifact)
        _run_grid(wd, "minifaults", extra=[*extra, "--resume"], metrics_out=m2)
        assert _read_bytes(artifact) == first

        a, b = _load(m1), _load(m2)
        assert a["comparable"] == b["comparable"]
        runs1 = metrics.series_map(a, "faults.unit_runs", "kind")
        runs2 = metrics.series_map(b, "faults.unit_runs", "kind")
        assert runs1.get("computed", 0) > 0 and "resumed" not in runs1
        assert runs2.get("resumed", 0) == runs1["computed"] and "computed" not in runs2
