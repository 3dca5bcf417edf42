"""A run with the timed path broken underneath comes out not correct: for
each fault the cell can have (`bench/faults.py`), planted where the program
produces it."""
import pytest

from bench import faults
from bench.tests.cases import run_tiny

# The number each fault has to fail, besides `correct` coming out false.
CAUGHT_BY = {
    "unchanged_step": "bfs_mismatch",
    "skip_descent": "placement_swap_gain",
    "half_grid": "answers_missing",
    "simulate_altered": "simulate_rel",
    "nocsim_altered": "nocsim_rel",
    "credit_altered": "nocsim_credit_rel",
}


def test_every_fault_is_named():
    assert set(CAUGHT_BY) == set(faults.FAULTS)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_sweep_fault_is_caught(monkeypatch, fault):
    faults.FAULTS[fault](monkeypatch.setattr)
    result = run_tiny("pokec.front", seconds=0.1)
    assert not result["correct"]
    assert result["failed"] >= 1
    check = result["checks"][CAUGHT_BY[fault]]
    assert check["value"] > check["limit"], (fault, check)
