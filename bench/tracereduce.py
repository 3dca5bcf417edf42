"""From a profiler trace to device busy time, kernel time and labelled gaps.

The JAX profiler writes one `.xplane.pb`; `jax.profiler.ProfileData` reads
it.  Each TPU chip is a plane `/device:TPU:<n>`, whose `XLA Modules` line
holds one event per executed program (its jit name and a run id) and whose
`XLA Ops` line holds one event per operation.  The host plane holds the
`bench.sync` annotation that the harness emitted at a known time of the
obs clock (`repro.obs.now_ns`), which puts the program's spans on the
trace's clock.

- busy: the union of a device's operation intervals inside the window;
- idle share: 1 − busy ÷ window, averaged over the devices;
- module time: the summed durations of a program's events;
- idle gaps: the stretches of the window in which device 0 ran nothing,
  each named by the innermost obs span that covers its middle.
"""
from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = "bench.sync"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_RUN_ID = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """A program's name without the run id the profiler appends."""
    return _RUN_ID.sub("", event_name).strip()


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, reach = 0, lo
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, reach = [], lo
    for a, b in sorted(intervals):
        if a > reach:
            out.append((reach, min(a, hi)))
        reach = max(reach, b)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(a, b) for a, b in out if b > a]


class Reduction:
    """One traced window, reduced.  Times are in the trace's nanoseconds;
    `offset_ns` turns an obs-clock time into trace time."""

    def __init__(self, devices: dict, lo: int, hi: int, spans, offset_ns: int):
        self.devices = devices  # id -> {"ops": [(name, a, b)], "modules": [(name, a, b)]}
        self.lo, self.hi = lo, hi
        self.spans = spans or []
        self.offset_ns = offset_ns
        self.window_s = (hi - lo) / 1e9
        busy = [union_ns(self._busy_intervals(d), lo, hi) for d in devices.values()]
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    def _busy_intervals(self, d) -> list[tuple[int, int]]:
        events = d["ops"] or d["modules"]
        return [(a, b) for _, a, b in events]

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def _mean_over_devices(self, key, pred) -> float:
        if not self.devices:
            return 0.0
        total = 0
        for d in self.devices.values():
            for name, a, b in d[key]:
                if pred(name):
                    total += max(0, min(b, self.hi) - max(a, self.lo))
        return total / len(self.devices) / 1e9

    def module_time_s(self, pred) -> float:
        """Device seconds, averaged over the devices, of the programs whose
        name satisfies `pred`."""
        return self._mean_over_devices("modules", lambda n: pred(module_name(n)))

    def op_time_s(self, pred) -> float:
        """Device seconds, averaged over the devices, of the operations whose
        name satisfies `pred`."""
        return self._mean_over_devices("ops", pred)

    def module_runs(self, pred) -> int:
        """How many times, summed over the devices, such programs ran."""
        return sum(
            1 for d in self.devices.values() for name, a, b in d["modules"]
            if pred(module_name(name)) and a >= self.lo and b <= self.hi
        )

    def label(self, t_trace: int) -> str:
        """The innermost obs span that covers a trace time."""
        t = t_trace - self.offset_ns
        best = None
        for s in self.spans:
            if s.start_ns <= t < s.start_ns + s.dur_ns and (best is None or s.dur_ns < best.dur_ns):
                best = s
        return best.name if best is not None else "outside any span"

    def breakdown(self, top: int = 10) -> dict:
        """The programs that took most device time, and the idle time of
        device 0 summed by what the host was doing (the covering span)."""
        per_module: dict[str, float] = {}
        for d in self.devices.values():
            for name, a, b in d["modules"]:
                dur = max(0, min(b, self.hi) - max(a, self.lo))
                per_module[module_name(name)] = per_module.get(module_name(name), 0) + dur
        n = max(1, len(self.devices))
        device_ops = sorted(((k, v / n / 1e9) for k, v in per_module.items()), key=lambda kv: -kv[1])
        idle: dict[str, float] = {}
        if self.devices:
            d0 = self.devices[min(self.devices)]
            for a, b in gaps(self._busy_intervals(d0), self.lo, self.hi):
                key = self.label((a + b) // 2)
                idle[key] = idle.get(key, 0.0) + (b - a) / 1e9
        idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])
        return {
            "device_ops": [[k, v] for k, v in device_ops[:top]],
            "idle_gaps": [[k, v] for k, v in idle_gaps[:top]],
        }


def _events(line) -> list[tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)) for ev in line.events]


def reduce(path: str, sync_ns: int, t0_ns: int, t1_ns: int, spans) -> Reduction:
    """Reduce the trace at `path`; `sync_ns` is the obs time at which the
    `bench.sync` annotation began, [t0_ns, t1_ns] the window on the obs
    clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    sync_trace = None
    devices = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices[int(m.group(1))] = {
                "ops": _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                "modules": _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            }
            continue
        if sync_trace is None and plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC:
                        sync_trace = int(ev.start_ns)
                        break
    if sync_trace is None:
        raise RuntimeError(f"no {SYNC} annotation in the trace")
    offset = sync_trace - sync_ns
    return Reduction(devices, t0_ns + offset, t1_ns + offset, spans, offset)
