"""One run of one cell: set-up, the measured window, the check, the result.

`run_cell` is what `bench/run.py` calls once it has found the chips; the
tests call it with CPU devices at a tiny size.  Nothing the check or the
readers do runs inside the window, and the window holds whole units only:
it closes at the end of the first unit that ends at or after `seconds`.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import shutil
import sys
import tempfile
import time

from bench import common


@dataclasses.dataclass
class Context:
    """What a metric's reader reads.  `spans` (the program's obs spans in
    the window) and `device` (the reduced profiler trace) are set only in a
    `--trace 1` run."""

    cell: str
    config: dict
    mix: dict
    info: dict
    setup_s: float
    window_s: float
    units: list
    unit_edges: int
    peaks: dict
    spans: list | None = None
    device: object | None = None


def _say(device_name: str, msg: str) -> None:
    print(f"[{device_name}] {msg}", file=sys.stderr, flush=True)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, devices, *,
             bm: dict | None = None, cfg: dict | None = None, t_start: float | None = None,
             prepare=None, keep_units: dict | None = None) -> dict:
    """Run one cell and return its result object (the last line of stdout).
    `cfg` replaces the cell's configuration (tests run tiny sizes);
    `prepare(work)` runs after the work object is made, before set-up;
    `keep_units` receives the window's units under "units"."""
    import jax

    from repro import obs

    t_start = time.monotonic() if t_start is None else t_start
    bm = common.load_benchmark() if bm is None else bm
    cell = common.cell(bm, cell_name)
    cfg = common.config(bm, cell) if cfg is None else cfg
    mix = common.mix(cell)
    limits = common.limits(cell_name)
    dev = devices[0]
    name = f"{dev.device_kind} x{len(devices)}"
    peaks = common.peaks(dev.device_kind) if dev.platform == "tpu" else {}

    graph = common.make_graph(cfg, seed)
    _say(name, f"{cell_name} seed {seed}: |V|={graph.num_nodes} |E|={graph.num_edges}")
    work = common.work_class(mix["kind"])(cfg, mix, seed, graph, devices)
    if prepare is not None:
        prepare(work)
    try:
        work.setup()
        setup_s = time.monotonic() - t_start
        _say(name, f"set-up {setup_s:.3f} s")

        profile_dir = sync_ns = None
        if trace:
            obs.get_tracer().reset()
            obs.enable_tracing()
            profile_dir = tempfile.mkdtemp(prefix="bench-profile-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            sync_ns = obs.now_ns()
            with jax.profiler.TraceAnnotation("bench.sync"):
                pass
        units = []
        t0_ns = obs.now_ns()
        while True:
            with obs.span("bench.unit", cat="bench") as sp:
                units.append(work.unit())
            t1_ns = obs.now_ns()
            _say(name, f"unit {len(units)}: {sp.duration_s:.3f} s")
            if (t1_ns - t0_ns) / 1e9 >= seconds:
                break
        window_s = (t1_ns - t0_ns) / 1e9
        spans = device = None
        if trace:
            jax.profiler.stop_trace()
            obs.disable_tracing()
            spans = [s for s in obs.get_tracer().spans() if s.start_ns >= t0_ns and s.start_ns < t1_ns]
        _say(name, f"window {window_s:.3f} s, {len(units)} units")

        memory_peak = 0
        for d in devices:
            stats = d.memory_stats() or {}
            memory_peak = max(memory_peak, int(stats.get("peak_bytes_in_use", 0)))

        if trace:
            from bench import tracereduce

            files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
            if len(files) != 1:
                raise RuntimeError(f"the profiler wrote {len(files)} trace files, not one")
            device = tracereduce.reduce(files[0], sync_ns, t0_ns, t1_ns, spans)
            shutil.rmtree(profile_dir, ignore_errors=True)

        t_check = time.monotonic()
        per_unit = work.check(units, seed)
        _say(name, f"check {time.monotonic() - t_check:.3f} s")
        ctx = Context(cell_name, cfg, mix, work.info, setup_s, window_s, units,
                      len(units) * graph.num_edges, peaks, spans, device)
        if keep_units is not None:
            keep_units["units"] = units
    finally:
        work.close()

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for entry in common.metrics_of(bm, cell_name, kind):
        value = common.reader(entry["name"])(ctx)
        if value is None:
            continue
        if not math.isfinite(value):
            raise RuntimeError(f"metric {entry['name']} read {value}")
        metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    numbers: dict[str, float] = {}
    failed_units = 0
    for nums in per_unit:
        for key, value in nums.items():
            if key not in limits:
                raise KeyError(f"bench/limits/{cell_name}.json has no limit for {key}")
            numbers[key] = max(numbers.get(key, value), value)
        failed_units += any(not value <= limits[key] for key, value in nums.items())
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise RuntimeError(f"the check compared no {missing}, which bench/limits/{cell_name}.json limits")
    checks = {key: {"value": numbers[key], "limit": limits[key]} for key in sorted(numbers)}
    failed_checks = [key for key, c in checks.items() if not c["value"] <= c["limit"]]

    result = {
        "correct": not failed_checks,
        "attempted": len(units),
        "failed": failed_units,
        "metrics": metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak,
        },
    }
    if trace:
        result["device"]["busy_s"] = device.busy_s
        result["device"]["window_s"] = device.window_s
        result["breakdown"] = device.breakdown()
    result["checks"] = checks
    for key, c in checks.items():
        verdict = "ok" if key not in failed_checks else "FAILED"
        _say(name, f"check {key} = {c['value']!r} (limit {c['limit']!r}) {verdict}")
    return result
