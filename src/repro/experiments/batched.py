"""Vectorized evaluation hot path: `simulate()` and placement scoring batched
over all sweep configurations at once.

The serial simulator (`repro.core.simulator.simulate`) walks every traffic
flow in a Python loop to accumulate per-link loads — fine for one config,
dominant for a 48-config sweep.  Here the whole batch is evaluated with three
tensor contractions over stacked `(n_configs, 4P, 4P)` arrays:

  1. scatter each config's logical-shard traffic into *router space* using
     its placement:  B[c, site_i, site_j] = bytes[i, j]   (placements are
     injective, so this is a pure permutation-scatter);
  2. byte-hops:      bh[c]   = Σ_st B[c,s,t] · D[s,t]     (one einsum, D is
     the shared distance matrix of the batch's topology);
  3. link loads:     load[c] = B[c].reshape(-1) @ Rᵀ      (R is the routing
     operator: R[l, s·N+t] = 1 iff link l lies on the X-Y route s→t),
     peak[c] = max_l load[c,l].

Everything downstream of (bh, peak, total_bytes) is elementwise over the
batch.  The routing operator reproduces `_per_link_peak_load` exactly: X-Y
dimension-ordered stepping for 2-D coordinate meshes, direct per-dimension
links for the flattened butterfly, and the uniform-spread `byte_hops/links`
fallback for other topologies — so batched results equal the serial ones to
fp tolerance (tested in tests/test_experiments_sweep.py).

Backends: "numpy" (float64, bit-exact vs serial up to summation order) and
"jax" (`jax.jit`-compiled float32 contractions at
`repro.precision.DOT_PRECISION`, ≤ 1e-6 relative on CPU and TPU).  "auto"
picks jax past `JAX_AUTO_THRESHOLD` stacked elements, else numpy.
"""
from __future__ import annotations

import numpy as np

from repro.analysis.registry import parity_pair
from repro.core.noc import Topology
from repro.core.placement import Placement
from repro.core.simulator import SimParams, SimResult
from repro.core.traffic import SparseTraffic, TrafficMatrix
from repro.precision import DOT_PRECISION

__all__ = [
    "routing_operator",
    "scatter_to_router_space",
    "simulate_batch",
    "simulate_serial",
    "batched_weighted_hops",
    "resolve_backend",
]

# "auto" switches to jax only past this stacked-tensor element count: below it
# BLAS float64 einsums beat jit dispatch + f32 transfer (measured: a 48-config
# paper grid is ~100k elements/group and numpy wins; jax pays off when the
# batch no longer fits one BLAS call comfortably).
JAX_AUTO_THRESHOLD = 1 << 24


def resolve_backend(backend: str = "auto", problem_size: int | None = None) -> str:
    """Map "auto" to a concrete backend.  `problem_size` is the total element
    count of the stacked batch tensors, when the caller knows it."""
    if backend not in ("auto", "jax", "numpy"):
        raise ValueError(f"unknown backend {backend!r}; options: auto|jax|numpy")
    if backend != "auto":
        return backend
    try:
        import jax  # noqa: F401
    except ImportError:  # pragma: no cover - jax is baked into the container
        return "numpy"
    if problem_size is not None and problem_size < JAX_AUTO_THRESHOLD:
        return "numpy"
    return "jax"


def routing_operator(topology: Topology):
    """(num_links, N·N) sparse CSR operator mapping a router-space bytes
    matrix to per-link loads, built from the same `Topology.route_links`
    model the serial simulator uses (X-Y mesh stepping, flattened-butterfly
    direct links, wraparound torus stepping) — so batched and serial link
    loads cannot drift apart.  Sparse because a route touches only
    `hops(s,t)` of the L links (~0.5 % of entries on an 8×8 mesh) — the
    dense matmul was the batch hot spot.

    The operator itself is the natural-order half of the pair
    `repro.nocsim.routes.route_operators` builds (one builder, one cache —
    the windowed contention simulator shares it); links only the reversed
    order uses carry zero load under this operator and cannot be the peak.
    Returns None for topologies with no exact route_links — none of the
    built-in four since Torus3D gained wrap-aware dimension-ordered routing
    — which the batched path approximates with the uniform spread, like the
    serial one.
    """
    from repro.nocsim.routes import route_operators

    ops = route_operators(topology)
    return None if ops is None else ops.nat


def scatter_to_router_space(
    traffic: TrafficMatrix | SparseTraffic, placement: Placement
) -> np.ndarray:
    """(N, N) bytes between *routers* under `placement` (N = topology nodes).
    Accepts the COO form directly (scatters only the nonzeros — the pairs are
    unique by construction, so the result equals the dense scatter)."""
    n = placement.topology.num_nodes
    out = np.zeros((n, n), dtype=np.float64)
    s = placement.site
    if isinstance(traffic, SparseTraffic):
        out[s[traffic.rows], s[traffic.cols]] = traffic.vals
    else:
        out[np.ix_(s, s)] = traffic.bytes_matrix
    return out


def _results_from_scalars(
    total_bytes: np.ndarray,
    byte_hops: np.ndarray,
    peak_link: np.ndarray,
    num_parts: int,
    num_iterations: np.ndarray,
    params: SimParams,
) -> list[SimResult]:
    """The elementwise tail of `simulate()` over the batch, in float64."""
    total_bytes = np.asarray(total_bytes, dtype=np.float64)
    byte_hops = np.asarray(byte_hops, dtype=np.float64)
    peak_link = np.asarray(peak_link, dtype=np.float64)
    it = np.asarray(num_iterations, dtype=np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        avg_hops = np.where(total_bytes > 0, byte_hops / total_bytes, 0.0)
    total_packets = total_bytes / params.packet_bytes
    per_engine_packets = total_packets / max(1, num_parts)
    t_compute = (
        it * 2 * params.cam_search_cycles / params.engine_freq_hz
        + per_engine_packets / params.alu_lanes / params.engine_freq_hz
    )
    t_sf = per_engine_packets * avg_hops * params.hop_latency_s
    t_serial = peak_link / params.link_bandwidth_bytes_per_s
    t_latency = it * avg_hops * params.hop_latency_s
    t_network = np.maximum(t_sf, t_serial) + t_latency
    exec_time = t_compute + t_network
    e_network = (
        byte_hops * params.e_per_hop_per_byte_j
        + total_packets * (avg_hops + 1.0) * params.e_router_per_packet_j
    )
    searches = it * 2 * num_parts
    e_compute = searches * params.e_cam_search_j + total_packets * params.e_alu_per_op_j
    energy = e_network + e_compute + params.e_static_w * exec_time
    return [
        SimResult(
            exec_time_s=float(exec_time[c]),
            energy_j=float(energy[c]),
            avg_hops=float(avg_hops[c]),
            total_bytes=float(total_bytes[c]),
            byte_hops=float(byte_hops[c]),
            t_compute_s=float(t_compute[c]),
            t_network_s=float(t_network[c]),
            t_serialization_s=float(t_serial[c]),
            e_network_j=float(e_network[c]),
            e_compute_j=float(e_compute[c]),
        )
        for c in range(total_bytes.size)
    ]


def _contract_numpy(stack: np.ndarray, dist: np.ndarray, routing):
    total_bytes = stack.sum(axis=(1, 2))
    byte_hops = np.einsum("cst,st->c", stack, dist)
    if routing is not None:
        loads = routing @ stack.reshape(stack.shape[0], -1).T  # (L, C)
        peak = loads.max(axis=0) if loads.shape[0] else np.zeros(stack.shape[0])
    else:
        peak = None
    return total_bytes, byte_hops, peak


def _contract_numpy_blocked(stack: np.ndarray, dist: np.ndarray, routing, block: int):
    """`_contract_numpy` streamed over column blocks of the flattened (s, t)
    pair axis: total-bytes, byte-hops and link-load accumulation each touch
    O(C·block) (plus one (L, C) loads accumulator) per step instead of the
    full C·N² flat stack at once.  Traffic bytes are integer-valued and the
    routing operator is 0/1, so the per-block partial sums re-associate
    bit-exactly (see core.traffic's module docstring); `peak` is a max and
    unaffected by chunking."""
    c = stack.shape[0]
    flat = stack.reshape(c, -1)
    m = flat.shape[1]
    dflat = dist.reshape(-1)
    total_bytes = np.zeros(c, dtype=np.float64)
    byte_hops = np.zeros(c, dtype=np.float64)
    loads = (
        np.zeros((routing.shape[0], c), dtype=np.float64) if routing is not None else None
    )
    for start in range(0, m, block):
        sl = slice(start, min(start + block, m))
        total_bytes += flat[:, sl].sum(axis=1)
        byte_hops += flat[:, sl] @ dflat[sl]
        if routing is not None:
            loads += routing[:, sl] @ flat[:, sl].T
    if routing is None:
        peak = None
    elif loads.shape[0]:
        peak = loads.max(axis=0)
    else:
        peak = np.zeros(c)
    return total_bytes, byte_hops, peak


_JAX_KERNELS: dict[bool, object] = {}
# Dense copies of the (cached-forever) sparse routing operators for the jax
# matmul path, keyed by object id — safe because nocsim.routes._OP_CACHE
# pins them (routing_operator returns the cached pair's natural half).
_JAX_DENSE_ROUTING: dict[int, object] = {}


def _jax_contract_fn(with_routing: bool):
    """Build (once per arm) the jitted stacked contractions; jit
    re-specialises per (C, N, L) group shape automatically."""
    kernel = _JAX_KERNELS.get(with_routing)
    if kernel is not None:
        return kernel
    import jax
    import jax.numpy as jnp

    if with_routing:

        def kernel(B, D, R):
            total = B.sum(axis=(1, 2))
            bh = jnp.einsum("cst,st->c", B, D, precision=DOT_PRECISION)
            loads = jnp.matmul(B.reshape(B.shape[0], -1), R.T, precision=DOT_PRECISION)
            return total, bh, loads.max(axis=1)

    else:

        def kernel(B, D):
            total = B.sum(axis=(1, 2))
            bh = jnp.einsum("cst,st->c", B, D, precision=DOT_PRECISION)
            return total, bh

    kernel = _JAX_KERNELS[with_routing] = jax.jit(kernel)
    return kernel


def _contract_jax(stack: np.ndarray, dist: np.ndarray, routing):
    import jax.numpy as jnp

    with_routing = routing is not None
    if with_routing:
        dense = _JAX_DENSE_ROUTING.get(id(routing))
        if dense is None:
            dense = _JAX_DENSE_ROUTING[id(routing)] = jnp.asarray(routing.toarray())
        routing = dense
    kernel = _jax_contract_fn(with_routing)
    if with_routing:
        total, bh, peak = kernel(stack, dist.astype(np.float64), routing)
        return np.asarray(total, np.float64), np.asarray(bh, np.float64), np.asarray(peak, np.float64)
    total, bh = kernel(stack, dist.astype(np.float64))
    return np.asarray(total, np.float64), np.asarray(bh, np.float64), None


@parity_pair(
    serial="repro.core.simulator.simulate",
    kind="rel",
    note="equal to float64 tolerance per config (same routing model via "
    "`Topology.route_links`; numpy backend bit-exact up to summation "
    "order, jax f32 within the gate)",
)
def simulate_batch(
    traffics: list[TrafficMatrix | SparseTraffic],
    placements: list[Placement],
    *,
    params: SimParams = SimParams(),
    num_iterations: np.ndarray | list[int] | int = 1,
    backend: str = "auto",
    pair_block: int | None = None,
) -> list[SimResult]:
    """Batched `simulate()`: one SimResult per (traffic, placement) pair.

    Pairs are grouped by (topology, num_parts) — each group shares one
    distance matrix and one routing operator — and each group is evaluated
    with the three stacked contractions described in the module docstring.
    Results are returned in input order and match the serial simulator to fp
    tolerance (float64-exact on the numpy backend).

    Traffics may be `SparseTraffic` (scattered from the COO directly).
    `pair_block` streams the contractions over column blocks of that many
    (s, t) router pairs (`_contract_numpy_blocked`) — bit-identical on the
    integer-byte domain and numpy-only, so setting it forces the numpy
    backend.
    """
    if len(traffics) != len(placements):
        raise ValueError("traffics and placements must pair up")
    n = len(traffics)
    iters = np.broadcast_to(np.asarray(num_iterations, dtype=np.int64), (n,))
    problem_size = sum(p.topology.num_nodes ** 2 for p in placements)
    if pair_block is not None:
        backend = "numpy"
    else:
        backend = resolve_backend(backend, problem_size)
    contract = _contract_jax if backend == "jax" else _contract_numpy

    groups: dict[tuple, list[int]] = {}
    for idx, (t, p) in enumerate(zip(traffics, placements)):
        groups.setdefault((p.topology, t.num_parts), []).append(idx)

    results: list[SimResult | None] = [None] * n
    for (topology, num_parts), idxs in groups.items():
        stack = np.stack(
            [scatter_to_router_space(traffics[i], placements[i]) for i in idxs]
        )
        dist = topology.distance_matrix().astype(np.float64)
        routing = routing_operator(topology)
        if pair_block is not None:
            total_bytes, byte_hops, peak = _contract_numpy_blocked(
                stack, dist, routing, max(1, int(pair_block))
            )
        else:
            total_bytes, byte_hops, peak = contract(stack, dist, routing)
        if peak is None:  # serial fallback: uniform spread over all links
            nlinks = max(1, topology.num_links())
            peak = byte_hops / nlinks
        for pos, res in zip(
            idxs,
            _results_from_scalars(total_bytes, byte_hops, peak, num_parts, iters[idxs], params),
        ):
            results[pos] = res
    return results  # type: ignore[return-value]


def simulate_serial(
    traffics: list[TrafficMatrix],
    placements: list[Placement],
    *,
    params: SimParams = SimParams(),
    num_iterations: np.ndarray | list[int] | int = 1,
) -> list[SimResult]:
    """The one-config-at-a-time loop the batch path replaces (reference +
    §Perf timing baseline)."""
    from repro.core.simulator import simulate

    n = len(traffics)
    iters = np.broadcast_to(np.asarray(num_iterations, dtype=np.int64), (n,))
    return [
        simulate(t, p, params=params, num_iterations=int(it))
        for t, p, it in zip(traffics, placements, iters)
    ]


def batched_weighted_hops(
    weights: np.ndarray,
    sites: np.ndarray,
    topology: Topology,
    *,
    backend: str = "auto",
) -> np.ndarray:
    """Placement scoring H = Σ_ij w_ij · dist(site_i, site_j) for a stack of
    placements at once: `weights` is (C, n, n) (or (n, n), broadcast over the
    site stack), `sites` is (C, n).  Returns (C,) scores — equal to
    `Placement.weighted_hops` per row."""
    sites = np.asarray(sites, dtype=np.int64)
    if sites.ndim != 2:
        raise ValueError("sites must be (n_configs, n_logical)")
    weights = np.asarray(weights, dtype=np.float64)
    if weights.ndim == 2:
        weights = np.broadcast_to(weights, (sites.shape[0],) + weights.shape)
    dist = topology.distance_matrix().astype(np.float64)
    if resolve_backend(backend) == "jax":
        import jax.numpy as jnp

        d = jnp.asarray(dist)[sites[:, :, None], sites[:, None, :]]
        h = jnp.einsum("cij,cij->c", jnp.asarray(weights), d, precision=DOT_PRECISION)
        return np.asarray(h, np.float64)
    d = dist[sites[:, :, None], sites[:, None, :]]
    return np.einsum("cij,cij->c", weights, d)
