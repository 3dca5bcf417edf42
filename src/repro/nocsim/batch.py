"""The windowed stepper, batched: numpy reference + one stacked jax program.

The window recursion per link is three elementwise ops —

    arrived  = backlog + injected
    serviced = min(arrived, cap)
    backlog  = arrived − serviced

— so the whole sweep stacks into (W, C, L_max) tensors: configs are padded
along the link axis to the largest link count in the batch (padded links
inject nothing and can never carry the per-window max), capacities are
normalised away per config (the recursion runs in units of one window's
service), and the jax backend advances ALL configs through ALL windows with
a single `jax.lax.scan` — no serial per-config Python loop, same parity
discipline as `experiments.placement_batch`:

  * numpy backend: float64, the reference semantics (windows loop in
    Python, configs vectorized);
  * jax backend: one jit-compiled f32 scan over the normalised recursion;
    min/add/sub on O(windows)-magnitude values keep the relative error well
    under the 1e-6 contract asserted per sweep (`contention_sweep_payload`
    records the measured numpy↔jax max relative difference on the contended
    T_network, and `repro.experiments.report --check` gates on it).

Everything before the recursion (`build_schedule`) and after it
(`assemble_result`) is shared float64 numpy, so backend disagreement is
attributable to the window recursion alone.
"""
from __future__ import annotations

import numpy as np

from repro import obs
from repro.analysis.registry import parity_pair
from repro.obs import span
from repro.core.placement import Placement
from repro.core.simulator import SimParams
from repro.core.traffic import TrafficMatrix
from repro.nocsim.model import (
    ConfigSchedule,
    NocSimParams,
    NocSimResult,
    assemble_result,
    build_schedule,
    normalize_buffer_depth,
)
from repro.nocsim.routes import ROUTING_POLICIES

__all__ = [
    "contended_batch",
    "contention_sweep_payload",
    "open_step",
    "run_windows",
    "PARITY_RTOL",
]

# Default window-chunk size when a caller asks for streaming without picking
# one: big enough to amortise dispatch, small enough to bound the stepper's
# working set.
DEFAULT_WINDOW_CHUNK = 64

# The numpy↔jax agreement contract on contended T_network, asserted per
# contention sweep and gated by `repro.experiments.report --check`.
PARITY_RTOL = 1e-6


def _resolve_backend(backend: str) -> str:
    if backend not in ("auto", "jax", "numpy"):
        raise ValueError(f"unknown backend {backend!r}; options: auto|jax|numpy")
    if backend != "auto":
        return backend
    try:
        import jax  # noqa: F401
    except ImportError:  # pragma: no cover - jax is baked into the container
        return "numpy"
    return "jax"


def _step_numpy(
    inj: np.ndarray, backlog0: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Reference recursion: `inj` is (W, C, L) in units of one window's
    service (cap ≡ 1); returns (serviced, backlog) timelines of the same
    shape.  Windows advance in a Python loop; configs and links are
    vectorized.  `backlog0` carries the state across window chunks (the
    recursion is strictly sequential over windows, so resuming it from the
    previous chunk's final backlog reproduces the unchunked timelines
    bit-for-bit — on both backends)."""
    w = inj.shape[0]
    backlog = (
        np.zeros(inj.shape[1:], dtype=np.float64) if backlog0 is None else backlog0.copy()
    )
    serviced_tl = np.empty_like(inj)
    backlog_tl = np.empty_like(inj)
    for step in range(w):
        arrived = backlog + inj[step]
        serviced = np.minimum(arrived, 1.0)
        backlog = arrived - serviced
        serviced_tl[step] = serviced
        backlog_tl[step] = backlog
    return serviced_tl, backlog_tl


_JAX_STEP = None


def _jax_step_fn():
    """Build (once) the jitted stacked stepper; jit re-specialises per
    (W, C, L_max) batch shape automatically."""
    global _JAX_STEP
    if _JAX_STEP is not None:
        return _JAX_STEP
    import jax
    import jax.numpy as jnp

    def run(inj, init):  # (W, C, L) normalised injections, cap ≡ 1
        def body(backlog, injected):
            arrived = backlog + injected
            serviced = jnp.minimum(arrived, 1.0)
            backlog = arrived - serviced
            return backlog, (serviced, backlog)

        _, (serviced_tl, backlog_tl) = jax.lax.scan(body, init, inj)
        return serviced_tl, backlog_tl

    _JAX_STEP = jax.jit(run)
    return _JAX_STEP


def _step_jax(
    inj: np.ndarray, backlog0: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray]:
    import jax.numpy as jnp

    init = (
        jnp.zeros(inj.shape[1:], dtype=jnp.float32)
        if backlog0 is None
        else jnp.asarray(backlog0, dtype=jnp.float32)
    )
    serviced, backlog = _jax_step_fn()(jnp.asarray(inj, dtype=jnp.float32), init)
    obs.count("dispatches")
    return np.asarray(serviced, np.float64), np.asarray(backlog, np.float64)


def _open_step_numpy(xs, carry):
    """`_step_numpy` in the `run_windows` step protocol (carry = backlog)."""
    s_tl, b_tl = _step_numpy(xs[0], carry)
    return (s_tl, b_tl), b_tl[-1]


def _open_step_jax(xs, carry):
    s_tl, b_tl = _step_jax(xs[0], carry)
    return (s_tl, b_tl), b_tl[-1]


def open_step(backend: str):
    """The open-loop stepper for one backend, in `run_windows` protocol."""
    return _open_step_jax if backend == "jax" else _open_step_numpy


def run_windows(step, xs: tuple, carry, *, window_chunk: int | None = None,
                on_chunk=None):
    """THE window-carry driver, shared by every stepper arm (open, credit,
    degraded segments): run `step` over the window axis in chunks of
    `window_chunk`, threading the arm's carry state between chunks.

    `step(xs_chunk, carry) -> (timelines, carry)` where `xs_chunk` is each
    input sliced along axis 0 and `timelines` is a tuple of window-axis
    arrays; `carry=None` means the arm's fresh initial state.  Every
    recursion here is strictly sequential over windows, so the chunk
    boundary state equals the unchunked run's state at that window and the
    chunked timelines are bit-identical on both backends for ANY chunk size
    (regression-tested at the adversarial sizes 1, W−1, W).  Because the
    arms share this one code path, `window_chunk=` cannot diverge between
    them.  The stepper's working set (and the jax transfer/scan extent) is
    bounded at O(chunk · state).

    `on_chunk(start_window, timelines)` is the flight-recorder tap: invoked
    AFTER each chunk's recursion completes (once, at window 0, for the
    unchunked path) with the chunk's materialized timelines.  It observes
    outputs only — never the carry, never inside a scan body — so it cannot
    perturb the recursion (RPL001) and sees identical data with any chunk
    size."""
    w = xs[0].shape[0]
    if window_chunk is None:
        tls, carry = step(tuple(xs), carry)
        if on_chunk is not None:
            on_chunk(0, tls)
        return tls, carry
    chunk = max(1, int(window_chunk))
    parts = []
    for start in range(0, w, chunk):
        tls, carry = step(tuple(x[start : start + chunk] for x in xs), carry)
        if on_chunk is not None:
            on_chunk(start, tls)
        parts.append(tls)
    stitched = tuple(
        np.concatenate([p[i] for p in parts]) for i in range(len(parts[0]))
    )
    return stitched, carry


@parity_pair(
    serial="repro.nocsim.model.simulate_contended",
    kind="rel",
    note="`simulate_contended` is a 1-config call into the same float64 "
    "numpy stepper (IS the reference); the stacked jax `lax.scan` agrees "
    "on contended T_network within 1e-6 relative, measured per contention "
    "sweep (`backend_parity_max_rel`) and gated by `report --check`",
)
def contended_batch(
    traffics: list[TrafficMatrix],
    placements: list[Placement],
    *,
    noc_params: NocSimParams = NocSimParams(),
    params: SimParams = SimParams(),
    num_iterations: np.ndarray | list[int] | int = 1,
    backend: str = "auto",
    schedules: list[ConfigSchedule] | None = None,
    window_chunk: int | None = None,
    config_keys: list[str] | None = None,
) -> list[NocSimResult]:
    """Batched contended simulation: one `NocSimResult` per (traffic,
    placement) pair, in input order.  All configs advance through one
    stacked recursion regardless of topology (the link axis is padded to
    the batch maximum).  `schedules` lets a caller running several backends
    over the same configs (the parity measurement) build them once.
    `window_chunk` streams the recursion over window chunks with the arm's
    carry state threaded between them — bit-identical to the unchunked run
    on both backends for any chunk size (see `run_windows`).  With
    `noc_params.flow_control == "credit"` the closed-loop stepper
    (`nocsim.credit`) runs instead of the open-loop recursion; its
    effective backlog (per-link buffer + at-source holdback mapped over the
    route) feeds the same `assemble_result` post-processing.

    When `noc_params` carries a flight recorder (constructed with
    `NocSimParams(record_timeline=...)`) and the numpy reference backend
    runs, the per-window normalized timelines stream into it: the open
    loop taps `run_windows`' `on_chunk` boundary, the credit arm captures
    its materialized timelines post-hoc — never the jax carry, never a
    scan body (RPL001), and never the result values themselves, so
    recording on vs off returns bit-identical `NocSimResult`s (tested).
    `config_keys` names the recorder tracks (defaults to positional)."""
    if len(traffics) != len(placements):
        raise ValueError("traffics and placements must pair up")
    n_cfg = len(traffics)
    if n_cfg == 0:
        return []
    iters = np.broadcast_to(np.asarray(num_iterations, dtype=np.int64), (n_cfg,))
    backend = _resolve_backend(backend)
    if schedules is None:
        schedules = [
            build_schedule(t, p, noc_params=noc_params, params=params)
            for t, p in zip(traffics, placements)
        ]
    recorder = getattr(noc_params, "recorder", None)
    if recorder is not None and backend != "numpy":
        recorder = None  # record from the float64 reference arm only
    if noc_params.flow_control == "credit":
        from repro.nocsim.credit import build_credit_program, run_credit

        program = build_credit_program(schedules, noc_params)
        tl, _ = run_credit(program, backend=backend, window_chunk=window_chunk)
        serviced_tl, backlog_tl = tl.serviced, tl.eff_backlog
        if recorder is not None:
            recorder.capture_batch(
                schedules,
                serviced_tl,
                backlog_tl,
                start_window=0,
                arm=f"{noc_params.routing}+credit(d={noc_params.buffer_depth:g})",
                keys=config_keys,
            )
    else:
        w = noc_params.windows
        l_max = max(s.inj.shape[1] for s in schedules)
        inj = np.zeros((w, n_cfg, l_max), dtype=np.float64)
        for c, s in enumerate(schedules):
            if s.cap_bytes > 0.0:
                inj[:, c, : s.inj.shape[1]] = s.inj / s.cap_bytes
        on_chunk = None
        if recorder is not None:
            def on_chunk(start, tls, _scheds=schedules):
                recorder.capture_batch(
                    _scheds,
                    tls[0],
                    tls[1],
                    start_window=start,
                    arm=noc_params.routing,
                    keys=config_keys,
                )
        serviced_tl, backlog_tl = run_windows(
            open_step(backend), (inj,), None, window_chunk=window_chunk,
            on_chunk=on_chunk,
        )[0]
    results = []
    for c, s in enumerate(schedules):
        l = s.inj.shape[1]
        cap = s.cap_bytes
        results.append(
            assemble_result(
                s,
                serviced_tl[:, c, :l] * cap,
                backlog_tl[:, c, :l] * cap,
                noc_params=noc_params,
                params=params,
                num_iterations=int(iters[c]),
                backend=backend,
            )
        )
    return results


def contention_sweep_payload(
    configs: list,
    traffics: list[TrafficMatrix],
    placements: list[Placement],
    *,
    num_iterations: np.ndarray | list[int] | int = 1,
    params: SimParams = SimParams(),
    noc_params: NocSimParams = NocSimParams(),
    run_parity: bool = True,
    buffer_depths: tuple[float, ...] | None = None,
) -> dict:
    """The `--grid contention` sweep pass: every config × every routing arm
    through the windowed simulator, on BOTH backends when jax is available.

    Reported numbers come from the float64 numpy reference; the jax run
    exists to (a) measure the stacked-program wall time and (b) measure the
    backend parity `backend_parity_max_rel` = max over (config, arm) of the
    relative |numpy − jax| on the contended T_network — committed into the
    sweep artifact and gated ≤ `PARITY_RTOL` by the report freshness audit.
    `configs` are `SweepConfig`-like objects (need `.key` plus the axis
    fields); records join back to sweep records on `key`.

    `buffer_depths` adds the closed-loop credit arm (`nocsim.credit`): per
    routing arm, one extra record set per depth (tagged
    `flow_control="credit"` / `buffer_depth`), folded into the same parity
    measurement — plus the infinite-credit convergence audit: a
    `buffer_depth=inf` credit run must reproduce the open-loop records
    bit-identically on numpy (`credit_inf_numpy_max_abs == 0.0`) and within
    the parity contract on jax (`credit_inf_jax_max_rel ≤ PARITY_RTOL`),
    both committed into the artifact and gated by `report --check`."""
    import dataclasses as _dc

    n_cfg = len(traffics)
    iters = np.broadcast_to(np.asarray(num_iterations, dtype=np.int64), (n_cfg,))
    records: list[dict] = []
    parity_max = 0.0
    inf_np_max_abs = 0.0 if buffer_depths is not None else None
    inf_jax_max_rel = None
    timings: dict[str, float] = {}
    backends = ["numpy"]
    have_jax = False
    if run_parity:
        try:
            import jax  # noqa: F401

            have_jax = True
            backends.append("jax")
        except ImportError:  # pragma: no cover
            pass

    def run_arm(arm_params, schedules, tag):
        nonlocal parity_max
        with span(f"nocsim.{tag}.numpy", cat="nocsim", configs=n_cfg) as sp:
            ref = contended_batch(
                traffics,
                placements,
                noc_params=arm_params,
                params=params,
                num_iterations=iters,
                backend="numpy",
                schedules=schedules,
            )
        timings[f"{tag}_numpy_s"] = sp.duration_s
        acc = None
        if have_jax:
            with span(f"nocsim.{tag}.jax", cat="nocsim", configs=n_cfg) as sp:
                acc = contended_batch(
                    traffics,
                    placements,
                    noc_params=arm_params,
                    params=params,
                    num_iterations=iters,
                    backend="jax",
                    schedules=schedules,
                )
            timings[f"{tag}_jax_s"] = sp.duration_s
            for r_np, r_jx in zip(ref, acc):
                denom = max(abs(r_np.t_network_contended_s), 1e-300)
                parity_max = max(
                    parity_max,
                    abs(r_np.t_network_contended_s - r_jx.t_network_contended_s) / denom,
                )
        return ref, acc

    for routing in ROUTING_POLICIES:
        arm_params = _dc.replace(noc_params, routing=routing)
        schedules = [
            build_schedule(t, p, noc_params=arm_params, params=params)
            for t, p in zip(traffics, placements)
        ]
        ref, acc = run_arm(arm_params, schedules, routing)
        for cfg, res in zip(configs, ref):
            records.append({"key": cfg.key, **_dc.asdict(cfg), **res.to_dict()})
        if buffer_depths is None:
            continue
        # Closed-loop credit arm: one record set per buffer depth (the
        # schedules are flow-control-independent and reused verbatim).
        for depth in buffer_depths:
            cr_params = _dc.replace(
                arm_params,
                flow_control="credit",
                buffer_depth=normalize_buffer_depth(depth),
            )
            cref, _ = run_arm(cr_params, schedules, f"{routing}_credit_d{depth:g}")
            for cfg, res in zip(configs, cref):
                records.append({"key": cfg.key, **_dc.asdict(cfg), **res.to_dict()})
        # Infinite-credit convergence audit vs the open-loop records above
        # (depth None ≡ unbounded buffering ≡ the open loop, bit-for-bit).
        inf_params = _dc.replace(
            arm_params,
            flow_control="credit",
            buffer_depth=normalize_buffer_depth(None),
        )
        iref, iacc = run_arm(inf_params, schedules, f"{routing}_credit_inf")
        for r_o, r_i in zip(ref, iref):
            inf_np_max_abs = max(
                inf_np_max_abs,
                abs(r_o.t_network_contended_s - r_i.t_network_contended_s),
                abs(r_o.t_drain_s - r_i.t_drain_s),
                abs(r_o.mean_queue_delay_s - r_i.mean_queue_delay_s),
            )
        if acc is not None and iacc is not None:
            inf_jax_max_rel = inf_jax_max_rel or 0.0
            for r_o, r_i in zip(acc, iacc):
                denom = max(abs(r_o.t_network_contended_s), 1e-300)
                inf_jax_max_rel = max(
                    inf_jax_max_rel,
                    abs(r_o.t_network_contended_s - r_i.t_network_contended_s) / denom,
                )
    return {
        "noc_params": _dc.asdict(noc_params),
        "records": records,
        "backends": backends,
        "backend_parity_max_rel": parity_max if have_jax else None,
        "parity_rtol": PARITY_RTOL,
        "buffer_depths": list(buffer_depths) if buffer_depths is not None else None,
        "credit_inf_numpy_max_abs": inf_np_max_abs,
        "credit_inf_jax_max_rel": inf_jax_max_rel,
        "timings": timings,
    }
