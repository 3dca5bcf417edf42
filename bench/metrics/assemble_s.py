"""assemble_s: seconds per answer that run_sweep spends assembling its
answer (experiments/sweep.py): the self time of the sweep.records spans
(building the SweepRecords) and of the sweep.metrics spans (the metrics
registry and snapshot)."""
from bench.spans import per_unit_s


def read(ctx):
    parts = [per_unit_s(ctx, name) for name in ("sweep.records", "sweep.metrics")]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None
