"""The control at a size the CPU holds: the work one precision below the
float32 that the configuration states has to come out not correct.  On the
chip `bench/control.py` reads the same controls at the cells' own sizes."""
import ml_dtypes
import numpy as np

from bench import common, control
from bench.tests.cases import TINY, benchmark, devices_for, run_tiny


def _one_pass_bf16_dots(monkeypatch):
    """What default matmul precision does to `simulate_batch` on a TPU: one
    bfloat16 pass, i.e. the contraction's inputs rounded to bfloat16.  On
    the CPU `DOT_PRECISION = "default"` changes nothing, so it is spelled
    out here."""
    import repro.experiments.batched as batched

    real = batched._contract_jax

    def bf16(stack, dist, routing):
        return real(np.asarray(stack).astype(ml_dtypes.bfloat16).astype(np.float32), dist, routing)

    monkeypatch.setattr(batched, "_contract_jax", bf16)


def test_sweep_control_fails(monkeypatch):
    _one_pass_bf16_dots(monkeypatch)
    result = run_tiny("pokec.front", seconds=0.1, prepare=control.lower_precision)
    assert not result["correct"]
    limit = result["checks"]["simulate_rel"]["limit"]
    assert result["checks"]["simulate_rel"]["value"] > 3 * limit


def test_bf16_reference_fails_pagerank_and_nocsim():
    from bench.harness import run_cell

    kept, units = {}, {}
    result = run_cell("pokec.front", 5, 0.1, False, devices_for("pokec.front"), bm=benchmark(), cfg=dict(TINY),
                      prepare=lambda w: kept.setdefault("work", w), keep_units=units)
    assert result["correct"]
    readings = control.bf16_readings(kept["work"], units["units"])
    limits = common.limits("pokec.front")
    for key in ("pagerank_rel", "nocsim_rel", "nocsim_credit_rel"):
        assert readings[key] > 3 * limits[key], (key, readings[key])
