"""Differential fuzzing of the dual-backend nocsim steppers.

The parity contract gated in sweeps (≤ 1e-6 on the final scalars) could in
principle hide compensating per-window errors; this harness compares the
float64 numpy reference against the f32 stacked jax scan STATE-BY-STATE —
every window's serviced/backlog/buffer/source timeline — on seeded random
small traffic matrices, for the open arm, the credit arm across buffer
depths, and the composed degraded+credit arm (credit flow control through
a mid-replay link failure, PR 7's two-segment stepper).  Seeds go through
the vendored `_hypothesis_compat` runner so every example reproduces on
the offline container.

Identity cases (no fuzz tolerance): an empty fault set through the
two-segment degraded path must be bit-identical to the pristine credit
run, and the degraded arm at `buffer_depth=inf` must be bit-identical to
the degraded open-loop arm — composition cannot break the convergence
contracts.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.noc import Mesh2D, Torus2D, Torus3D
from repro.core.placement import Placement
from repro.core.traffic import TrafficMatrix
from repro.faults.degraded import degraded_batch
from repro.faults.model import FaultSet, sample_link_faults
from repro.nocsim import (
    NocSimParams,
    build_credit_program,
    contended_batch,
    open_step,
    run_credit,
    run_windows,
)
from repro.nocsim.batch import PARITY_RTOL
from repro.nocsim.model import build_schedule

jax = pytest.importorskip("jax")

# Per-window f32 state tolerance: the scan carries state in f32, so each
# element wanders by a few ulps OF THE TIMELINE'S SCALE (a backlog that
# drains to ~0 in f64 keeps an f32 residue proportional to its peak, not to
# its final value).  The bound is therefore scale-aware: rtol per element
# plus an atol of rtol × the reference's peak magnitude.  Real divergence —
# a dropped window, a mis-ordered reduction — shows up orders of magnitude
# above this.  The scalar contract (PARITY_RTOL) stays the sweep gate.
STATE_RTOL = 1e-5


def _assert_state_close(got, ref, *, err_msg=""):
    scale = max(1.0, float(np.max(np.abs(ref), initial=0.0)))
    np.testing.assert_allclose(
        got, ref, rtol=STATE_RTOL, atol=STATE_RTOL * scale, err_msg=err_msg
    )


def _traffic(parts: int, seed: int, density: float = 0.4) -> TrafficMatrix:
    rng = np.random.default_rng(seed)
    n = 4 * parts
    m = (rng.random((n, n)) < density) * rng.integers(1, 2000, size=(n, n)).astype(
        np.float64
    )
    np.fill_diagonal(m, 0.0)
    return TrafficMatrix(
        num_parts=parts,
        bytes_matrix=m,
        phase_bytes={"process": float(m.sum()), "reduce": 0.0, "apply": 0.0},
    )


def _setup(topo, seed):
    parts = topo.num_nodes // 4
    t = _traffic(parts, seed)
    rng = np.random.default_rng(seed + 1)
    site = rng.permutation(topo.num_nodes)[: t.num_logical].astype(np.int64)
    return t, Placement(topo, site, "test")


def _credit_program(topo, seed, *, depth, routing="dor", windows=32):
    noc = NocSimParams(
        windows=windows, routing=routing, flow_control="credit", buffer_depth=depth
    )
    t, pl = _setup(topo, seed)
    sched = build_schedule(t, pl, noc_params=noc)
    return build_credit_program([sched], noc)


class TestOpenArmPerWindow:
    @given(seed=st.integers(0, 100_000))
    @settings(max_examples=10)
    def test_timelines_match(self, seed):
        noc = NocSimParams()
        t, pl = _setup(Mesh2D(4, 4), seed)
        s = build_schedule(t, pl, noc_params=noc)
        inj = np.zeros((noc.windows, 1, s.inj.shape[1]))
        inj[:, 0, :] = s.inj / s.cap_bytes
        (s_np, b_np), _ = run_windows(open_step("numpy"), (inj,), None)
        (s_jx, b_jx), _ = run_windows(open_step("jax"), (inj,), None)
        _assert_state_close(s_jx, s_np)
        _assert_state_close(b_jx, b_np)


class TestCreditArmPerWindow:
    @given(
        seed=st.integers(0, 100_000),
        depth=st.sampled_from([0.5, 1.0, 2.0, 8.0]),
        topo=st.sampled_from([Mesh2D(4, 4), Torus2D(4, 4), Torus3D(3, 3, 2)]),
    )
    @settings(max_examples=12, deadline=None)
    def test_state_timelines_match(self, seed, depth, topo):
        program = _credit_program(topo, seed, depth=depth)
        tl_np, carry_np = run_credit(program, backend="numpy")
        tl_jx, carry_jx = run_credit(program, backend="jax")
        for name in ("serviced", "eff_backlog", "buf", "src", "admitted", "arrivals"):
            _assert_state_close(
                getattr(tl_jx, name),
                getattr(tl_np, name),
                err_msg=f"{name} drifted (seed={seed}, depth={depth}, {topo.name})",
            )
        _assert_state_close(carry_jx[0], carry_np[0])
        _assert_state_close(carry_jx[1], carry_np[1])

    @given(seed=st.integers(0, 100_000), depth=st.sampled_from([0.5, 2.0]))
    @settings(max_examples=8, deadline=None)
    def test_scalars_within_contract(self, seed, depth):
        t, pl = _setup(Torus2D(4, 4), seed)
        noc = NocSimParams(flow_control="credit", buffer_depth=depth)
        r_np = contended_batch([t], [pl], noc_params=noc, backend="numpy")[0]
        r_jx = contended_batch([t], [pl], noc_params=noc, backend="jax")[0]
        rel = abs(r_jx.t_network_contended_s - r_np.t_network_contended_s) / abs(
            r_np.t_network_contended_s
        )
        assert rel <= PARITY_RTOL


class TestDegradedCreditComposition:
    """Credit flow control through a mid-replay link failure: the composed
    two-segment stepper keeps both backends in lockstep and degrades to
    its exact identities at the edges of the knob space."""

    @given(seed=st.integers(0, 100_000), depth=st.sampled_from([0.5, 1.0, 4.0]))
    @settings(max_examples=8, deadline=None)
    def test_numpy_jax_parity_under_faults(self, seed, depth):
        topo = Mesh2D(4, 4)
        t, pl = _setup(topo, seed)
        faults = sample_link_faults(topo, 0.05, seed=seed + 7)
        noc = NocSimParams(flow_control="credit", buffer_depth=depth)
        r_np = degraded_batch([t], [pl], [faults], noc_params=noc, backend="numpy")[0]
        r_jx = degraded_batch([t], [pl], [faults], noc_params=noc, backend="jax")[0]
        rel = abs(r_jx.t_network_contended_s - r_np.t_network_contended_s) / abs(
            r_np.t_network_contended_s
        )
        assert rel <= PARITY_RTOL
        # The per-window bottleneck-utilization timeline, not just scalars.
        _assert_state_close(r_jx.util_timeline, r_np.util_timeline)

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_empty_faults_are_pristine_credit(self, backend):
        t, pl = _setup(Torus2D(4, 4), 21)
        noc = NocSimParams(flow_control="credit", buffer_depth=1.0)
        deg = degraded_batch([t], [pl], [FaultSet()], noc_params=noc, backend=backend)[0]
        ref = contended_batch([t], [pl], noc_params=noc, backend=backend)[0]
        # Two-segment stepping with a no-op boundary == the unchunked run.
        assert deg.t_network_contended_s == ref.t_network_contended_s
        assert deg.t_drain_s == ref.t_drain_s
        assert deg.mean_queue_delay_s == ref.mean_queue_delay_s
        np.testing.assert_array_equal(deg.util_timeline, ref.util_timeline)

    def test_degraded_infinite_credit_is_degraded_open(self):
        topo = Mesh2D(4, 4)
        t, pl = _setup(topo, 22)
        faults = sample_link_faults(topo, 0.1, seed=3)
        assert not faults.is_empty
        inf_noc = NocSimParams(flow_control="credit", buffer_depth=float("inf"))
        r_inf = degraded_batch([t], [pl], [faults], noc_params=inf_noc, backend="numpy")[0]
        r_open = degraded_batch([t], [pl], [faults], backend="numpy")[0]
        assert r_inf.t_network_contended_s == r_open.t_network_contended_s
        assert r_inf.t_drain_s == r_open.t_drain_s
        np.testing.assert_array_equal(r_inf.util_timeline, r_open.util_timeline)

    def test_backpressure_tightens_under_faults(self):
        # Sanity on the composed physics: a faulted fabric with tight
        # buffers cannot beat the same faulted fabric with infinite ones.
        topo = Mesh2D(4, 4)
        t, pl = _setup(topo, 23)
        faults = sample_link_faults(topo, 0.1, seed=5)
        times = []
        for depth in (0.5, 2.0, float("inf")):
            noc = NocSimParams(flow_control="credit", buffer_depth=depth)
            r = degraded_batch([t], [pl], [faults], noc_params=noc, backend="numpy")[0]
            times.append(r.t_network_contended_s)
        assert times[0] >= times[1] * (1 - 1e-12) >= times[2] * (1 - 1e-12) ** 2
