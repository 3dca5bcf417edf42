"""Plain float64 references for what a sweep answers.

Written from the semantics the repository documents (the paper's Algorithm 1
vertex programs, Algorithm 2 partitioning, the §4 traffic flows, Table 3's
network constants, the 2-opt neighbourhood of the placement search, the
windowed network model under both routing arms and both flow controls), in
straightforward numpy.  Nothing here imports the program or takes anything it made: the
graph comes from the benchmark's own generator, and a placement is the
answer under check, which the reference evaluates and never trusts.

`dtype` switches the float arithmetic of the pieces that a lower precision
would change (PageRank, the window recursion): float64 is the reference, a
lower type is the control that the check has to fail.
"""
from __future__ import annotations

import itertools
import math

import numpy as np

# The four structure shards of a part (paper §4), as logical id struct·P + part.
ET, VPROP, VTEMP, EPROP = range(4)
PACKET_BYTES = 8
# Phase of a flow by (from, to) structure: process, reduce, apply.
PHASE_OF = {(ET, VPROP): 0, (VPROP, EPROP): 0, (EPROP, VTEMP): 1, (ET, VTEMP): 1, (VTEMP, VPROP): 2}

# The simulated fabric's stated parameters: paper Table 3 (1 GHz NoC, 8-byte
# packets, 1 ns per hop), the GRAM engine at 100 MHz, and the energy
# constants the repository states for it (EXPERIMENTS.md §Calibration).
SIM = {
    "noc_freq_hz": 1e9,
    "hop_latency_s": 1e-9,
    "engine_freq_hz": 100e6,
    "cam_search_cycles": 4.0,
    "alu_lanes": 128.0,
    "e_per_hop_per_byte_j": 1.2e-12,
    "e_router_per_packet_j": 0.6e-12,
    "e_cam_search_j": 3.0e-9,
    "e_alu_per_op_j": 0.4e-12,
    "e_static_w": 0.02,
}
LINK_BYTES_PER_S = PACKET_BYTES * SIM["noc_freq_hz"]
# The windowed model's stated parameters: 32 windows, phase-resolved
# injection, offered rate 1.0 of link bandwidth.
NOC_WINDOWS = 32
NOC_INJ_RATE = 1.0
# PageRank stops after the first iteration whose L1 change is at most 1e-5;
# the sweep traces it for at most 40 iterations.
PAGERANK_TOL = 1e-5
SWEEP_PAGERANK_ITERATIONS = 40

SIM_FIELDS = (
    "exec_time_s", "energy_j", "avg_hops", "total_bytes", "byte_hops",
    "t_compute_s", "t_network_s", "t_serialization_s", "e_network_j", "e_compute_j",
)


# --------------------------------------------------------------- algorithms
def bfs(src, dst, n: int, source: int, max_iterations: int = 200):
    """Synchronous frontier BFS.  Returns (levels, iterations, edge_activity,
    vertex_activity): levels are float (inf where unreachable); an iteration
    is one sweep over the current frontier, the last one finding nothing."""
    level = np.full(n, np.inf)
    level[source] = 0.0
    frontier = np.zeros(n, bool)
    frontier[source] = True
    it = 0
    while frontier.any() and it < max_iterations:
        it += 1
        reached = dst[frontier[src]]
        new = np.unique(reached[np.isinf(level[reached])])
        level[new] = it
        frontier = np.zeros(n, bool)
        frontier[new] = True
    edge_activity = np.isfinite(level[src]).astype(np.float64)
    vertex_activity = np.isfinite(level).astype(np.float64)
    vertex_activity[source] = 0.0
    return level, it, edge_activity, vertex_activity


def pagerank(src, dst, n: int, *, iterations=None, max_iterations=SWEEP_PAGERANK_ITERATIONS, tol=PAGERANK_TOL,
             damping=0.85, dtype=np.float64):
    """Damped PageRank by power iteration, every vertex active every
    iteration; stops after the iteration whose L1 change is at most `tol`,
    or after exactly `iterations` when given.  Returns (ranks, iterations,
    edge_activity, vertex_activity, deltas), deltas[i] being the L1 change
    of iteration i + 1."""
    inv = (1.0 / np.maximum(np.bincount(src, minlength=n), 1)).astype(dtype)
    w = inv[src]
    base = dtype((1.0 - damping) / n)
    pr = np.full(n, 1.0 / n, dtype)
    vertex_activity = np.zeros(n)
    deltas = []
    stop = iterations if iterations is not None else max_iterations
    while len(deltas) < stop:
        agg = np.bincount(dst, weights=(pr[src] * w).astype(np.float64), minlength=n)
        new = (base + dtype(damping) * agg.astype(dtype)).astype(dtype)
        vertex_activity += new != pr
        deltas.append(float(np.abs(new.astype(np.float64) - pr.astype(np.float64)).sum()))
        pr = new
        if iterations is None and deltas[-1] <= tol:
            break
    it = len(deltas)
    return pr, it, np.full(src.size, float(it)), vertex_activity, deltas


def stop_violation(deltas, iterations: int, max_iterations: int, tol: float = PAGERANK_TOL) -> float:
    """How far, as a share of `tol`, stopping after `iterations` breaks the
    rule "stop after the first iteration whose L1 change is at most tol"
    under the float64 changes `deltas`: an earlier change at or under tol,
    or (short of the cap) a last change over it.  0 when it keeps the rule."""
    v = max((1.0 - d / tol for d in deltas[: iterations - 1]), default=0.0)
    if iterations < max_iterations:
        v = max(v, deltas[iterations - 1] / tol - 1.0)
    return max(v, 0.0)


# ---------------------------------------------------------------- partition
def powerlaw_partition(src, n: int, parts: int, balance_slack: float = 1.05):
    """Algorithm 2: vertices sorted by out-degree (descending, stable) and
    dealt cyclically; edges live with their source; a part over
    ceil(slack·|E|/P) edges spills the edges of its lowest-degree sources,
    in order, into the parts with room, lowest part id first."""
    deg = np.bincount(src, minlength=n)
    order = np.argsort(-deg, kind="stable")
    vpart = np.empty(n, np.int64)
    vpart[order] = np.arange(n) % parts
    epart = vpart[src].copy()
    max_size = int(math.ceil(balance_slack * src.size / parts))
    counts = np.bincount(epart, minlength=parts)
    over = np.nonzero(counts > max_size)[0]
    if over.size:
        free = max_size - counts
        spilled = []
        for p in over:
            idx = np.nonzero(epart == p)[0]
            idx = idx[np.argsort(deg[src[idx]], kind="stable")]
            spilled.append(idx[: counts[p] - max_size])
            free[p] = 0
        spill = np.concatenate(spilled)
        targets = np.nonzero(free > 0)[0]
        epart[spill] = np.repeat(targets, free[targets])[: spill.size]
    return vpart, epart


def random_partition(src, n: int, parts: int, seed: int = 0):
    """Uniform random vertex parts from numpy's generator at `seed`; edges
    live with their source."""
    vpart = np.random.default_rng(seed).integers(0, parts, size=n, dtype=np.int32)
    vpart = vpart.astype(np.int64)
    return vpart, vpart[src]


def partition(name: str, src, n: int, parts: int):
    if name == "powerlaw":
        return powerlaw_partition(src, n, parts)
    if name == "random":
        return random_partition(src, n, parts)
    raise ValueError(f"no reference for partitioner {name!r}")


# ------------------------------------------------------------------ traffic
def traffic(vpart, epart, src, edge_activity, vertex_activity, parts: int) -> np.ndarray:
    """(4P, 4P) bytes between structure shards, paper §4: per active edge,
    ET→vProp(src) and vProp(src)→eProp (process), eProp→vTemp and ET→vTemp
    of the edge's part (reduce); per applied vertex vTemp→vProp (apply)."""
    n4 = 4 * parts
    w = np.asarray(edge_activity, np.float64) * PACKET_BYTES
    ep = np.asarray(epart, np.int64)
    sp = np.asarray(vpart, np.int64)[src]
    m = np.zeros(n4 * n4)
    for a, pa, b, pb in ((ET, ep, VPROP, sp), (VPROP, sp, EPROP, ep),
                         (EPROP, ep, VTEMP, ep), (ET, ep, VTEMP, ep)):
        m += np.bincount((a * parts + pa) * n4 + b * parts + pb, weights=w, minlength=n4 * n4)
    vp = np.asarray(vpart, np.int64)
    wv = np.asarray(vertex_activity, np.float64) * PACKET_BYTES
    m += np.bincount((VTEMP * parts + vp) * n4 + VPROP * parts + vp, weights=wv, minlength=n4 * n4)
    return m.reshape(n4, n4)


# ----------------------------------------------------------------- topology
class Topology:
    """A k-ary 2-D mesh, 2-D torus or 3-D torus with one router per shard
    (4P routers, the most nearly square or cubic shape), dimension-ordered
    routing (natural or reversed order), and on a torus the shorter way
    round each ring (ties toward increasing coordinates)."""

    def __init__(self, kind: str, parts: int):
        n = 4 * parts
        if kind == "torus3d":
            kx = max(k for k in range(1, int(round(n ** (1 / 3))) + 1) if n % k == 0)
            rest = n // kx
            ky = math.isqrt(rest)
            while rest % ky:
                ky -= 1
            dims = (kx, ky, rest // ky)
        elif kind in ("mesh2d", "torus2d"):
            kx = math.isqrt(n)
            while n % kx:
                kx -= 1
            dims = (kx, n // kx)
        else:
            raise ValueError(f"no reference for topology {kind!r}")
        self.kind, self.dims, self.wrap = kind, dims, kind != "mesh2d"
        self.coords = np.array(list(itertools.product(*(range(k) for k in dims))))
        self.num_nodes = len(self.coords)
        diff = np.abs(self.coords[:, None, :] - self.coords[None, :, :])
        if self.wrap:
            diff = np.minimum(diff, np.array(dims) - diff)
        self.dist = diff.sum(-1)
        self._index = {tuple(c): i for i, c in enumerate(self.coords.tolist())}
        self._routes: dict[tuple[int, int, bool], list[int]] = {}

    def route(self, a: int, b: int, reverse: bool = False) -> list[int]:
        """Links from router a to router b, each as the id from·N + to:
        dimension by dimension in increasing order (decreasing where
        `reverse`), one hop at a time."""
        key = (a, b, reverse)
        if key in self._routes:
            return self._routes[key]
        pos = list(self.coords[a])
        goal = self.coords[b]
        links = []
        order = range(len(self.dims) - 1, -1, -1) if reverse else range(len(self.dims))
        for d in order:
            k = self.dims[d]
            if self.wrap:
                fwd, bwd = (goal[d] - pos[d]) % k, (pos[d] - goal[d]) % k
                step, hops = (1, fwd) if fwd <= bwd else (-1, bwd)
            else:
                step, hops = (1 if goal[d] >= pos[d] else -1), abs(goal[d] - pos[d])
            for _ in range(hops):
                nxt = list(pos)
                nxt[d] = (pos[d] + step) % k if self.wrap else pos[d] + step
                links.append(self._index[tuple(pos)] * self.num_nodes + self._index[tuple(nxt)])
                pos = nxt
        self._routes[key] = links
        return links


def _pairs(routes: list[list[int]]) -> tuple[np.ndarray, np.ndarray]:
    """(link, flow) of every link on every flow's route, in flow order."""
    pf = np.repeat(np.arange(len(routes)), [len(r) for r in routes])
    pl = np.fromiter(itertools.chain.from_iterable(routes), np.int64, count=pf.size)
    return pl, pf


def _route_max(values: np.ndarray, pl: np.ndarray, pf: np.ndarray, flows: int) -> np.ndarray:
    out = np.zeros(flows)
    np.maximum.at(out, pf, values[pl])
    return out


def _flows(m: np.ndarray, sites: np.ndarray, topo: Topology, routing: str = "dor"):
    """The placed flows and their routes: (ii, jj, bytes, hops, pl, pf,
    links), with `pl`/`pf` the (link, flow) pairs of the routes over link
    ids 0..links-1.  Routing "dor" takes every flow's natural dimension
    order; "adaptive2" takes the reversed order where its most loaded link
    is strictly lighter than the natural one's, the loads estimated with
    every flow split half and half over both orders."""
    ii, jj = np.nonzero(m)
    w = m[ii, jj]
    a, b = sites[ii].tolist(), sites[jj].tolist()
    routes = [topo.route(x, y) for x, y in zip(a, b)]
    if routing == "adaptive2":
        rev = [topo.route(x, y, reverse=True) for x, y in zip(a, b)]
        (nl, nf), (rl, rf) = _pairs(routes), _pairs(rev)
        size = topo.num_nodes ** 2
        est = 0.5 * (np.bincount(nl, weights=w[nf], minlength=size)
                     + np.bincount(rl, weights=w[rf], minlength=size))
        take_rev = _route_max(est, rl, rf, w.size) < _route_max(est, nl, nf, w.size)
        routes = [r if t else n for n, r, t in zip(routes, rev, take_rev.tolist())]
    elif routing != "dor":
        raise ValueError(f"no reference for routing {routing!r}")
    pl, pf = _pairs(routes)
    _, pl = np.unique(pl, return_inverse=True)
    links = int(pl.max()) + 1 if pl.size else 1
    return ii, jj, w, topo.dist[sites[ii], sites[jj]].astype(np.float64), pl, pf, links


def placement_problems(sites: np.ndarray, topo: Topology, random_seed=None) -> int:
    """How many ways the placement is not a placement: sites outside the
    fabric or shared by two shards, and for the random baseline, sites that
    differ from numpy's permutation at its seed."""
    bad = int(((sites < 0) | (sites >= topo.num_nodes)).sum())
    bad += int(sites.size - np.unique(sites).size)
    if random_seed is not None:
        want = np.random.default_rng(random_seed).permutation(topo.num_nodes)[: sites.size]
        bad += int((want != sites).sum())
    return bad


def simulate(m: np.ndarray, sites: np.ndarray, topo: Topology, iterations: int, parts: int) -> dict:
    """The analytic model: T = compute + max(store-and-forward, peak-link
    serialization) + head latency; energy from byte-hops, packets, CAM
    searches and leakage."""
    ii, jj, w, hops, pl, pf, links = _flows(m, sites, topo)
    total = float(m.sum())
    byte_hops = float((w * hops).sum())
    peak = float(np.bincount(pl, weights=w[pf], minlength=links).max()) if w.size else 0.0
    avg_hops = byte_hops / total if total else 0.0
    packets = total / PACKET_BYTES
    per_engine = packets / max(1, parts)
    t_compute = (iterations * 2 * SIM["cam_search_cycles"] / SIM["engine_freq_hz"]
                 + per_engine / SIM["alu_lanes"] / SIM["engine_freq_hz"])
    t_sf = per_engine * avg_hops * SIM["hop_latency_s"]
    t_serial = peak / LINK_BYTES_PER_S
    t_network = max(t_sf, t_serial) + iterations * avg_hops * SIM["hop_latency_s"]
    exec_time = t_compute + t_network
    e_network = byte_hops * SIM["e_per_hop_per_byte_j"] + packets * (avg_hops + 1.0) * SIM["e_router_per_packet_j"]
    e_compute = iterations * 2 * parts * SIM["e_cam_search_j"] + packets * SIM["e_alu_per_op_j"]
    return {
        "exec_time_s": exec_time,
        "energy_j": e_network + e_compute + SIM["e_static_w"] * exec_time,
        "avg_hops": avg_hops,
        "total_bytes": total,
        "byte_hops": byte_hops,
        "t_compute_s": t_compute,
        "t_network_s": t_network,
        "t_serialization_s": t_serial,
        "e_network_j": e_network,
        "e_compute_j": e_compute,
    }


def _window_share(phase_bytes: np.ndarray, windows: int) -> np.ndarray:
    """(W, 3): each phase injects in its own block of windows, in phase
    order, with blocks ∝ phase bytes (at least one window per phase with
    bytes, the rounding remainder to the largest phase)."""
    share = np.zeros((windows, 3))
    total = float(phase_bytes.sum())
    active = phase_bytes > 0
    if total <= 0 or windows < int(active.sum()):
        share[:] = 1.0 / windows
        return share
    alloc = active.astype(np.int64)
    rest = windows - int(alloc.sum())
    alloc += np.floor(np.where(active, phase_bytes / total, 0.0) * rest).astype(np.int64)
    alloc[int(np.argmax(phase_bytes))] += windows - int(alloc.sum())
    start = 0
    for ph in range(3):
        if alloc[ph]:
            share[start:start + alloc[ph], ph] = 1.0 / alloc[ph]
            start += alloc[ph]
    return share


def swap_gain(m: np.ndarray, sites: np.ndarray, topo: Topology) -> float:
    """The largest share of H that one change of the placement still saves:
    two shards trading routers, or one shard moving to a free router.  0 at
    a local optimum of that neighbourhood, which the searched placements
    claim to be."""
    w = m + m.T
    np.fill_diagonal(w, 0.0)
    d = topo.dist.astype(np.float64)
    dss = d[np.ix_(sites, sites)]
    h = 0.5 * float((w * dss).sum())
    if h <= 0:
        return 0.0
    # trading i and j changes each other shard k's bytes with them from
    # hops to the old router to hops to the new one
    b = w @ dss
    r = np.diag(b)
    swap = b + b.T - r[:, None] - r[None, :] + 2.0 * w * dss
    np.fill_diagonal(swap, np.inf)
    best = float(swap.min())
    free = np.setdiff1d(np.arange(topo.num_nodes), sites)
    if free.size:
        move = w @ d[np.ix_(sites, free)] - r[:, None]
        best = min(best, float(move.min()))
    return max(0.0, -best) / h


def contended(m: np.ndarray, sites: np.ndarray, topo: Topology, iterations: int, parts: int, *,
              routing: str = "dor", depth: float | None = None, dtype=np.float64) -> dict:
    """The windowed network model: each phase's flows inject over their
    block of windows, and every link serves one window's worth of its
    peak-link budget per window.

    Open loop (`depth` None): a link carries what it cannot serve over to
    the next window.  Credit flow control (`depth` in windows of service,
    inf allowed): a flow injects its pending bytes only as far as every
    link on its route has buffer room for them, each link's room shared in
    proportion to what its flows ask; what is not admitted waits at the
    source and asks again next window, and room freed by service shows the
    window after.  The backlog is then the buffers plus the bytes waiting at
    the sources, over their routes.

    T = max(store-and-forward, drain) + head latency + the mean queueing
    delay of a byte.  Returns t_network_contended_s and t_drain_s."""
    ii, jj, w, hops, pl, pf, links = _flows(m, sites, topo, routing)
    phase = np.array([PHASE_OF.get((a // parts, b // parts), 0) for a, b in zip(ii, jj)], np.int64)
    phase_bytes = np.bincount(phase, weights=w, minlength=3)
    share = _window_share(phase_bytes, NOC_WINDOWS)
    loads = np.bincount(pl, weights=w[pf], minlength=links)
    cap = float(loads.max()) / (NOC_WINDOWS * NOC_INJ_RATE)
    offered = share[:, phase] * w[None, :] / cap  # (W, F) in windows of service

    def over_links(per_flow):
        return np.bincount(pl, weights=np.asarray(per_flow, np.float64)[pf], minlength=links).astype(dtype)

    one = dtype(1.0)
    buf = np.zeros(links, dtype)
    held = np.zeros(w.size, dtype)
    serviced_t, backlog_t = [], []
    for offered_w in offered.astype(dtype):
        if depth is None:
            arrivals = over_links(offered_w)
        else:
            demand = held + offered_w
            asked = over_links(demand)
            room = np.maximum(dtype(depth) - buf, dtype(0.0))
            ratio = np.where(asked > 0, np.minimum(one, room / np.where(asked > 0, asked, one)), one)
            gate = np.ones(w.size)
            np.minimum.at(gate, pf, ratio[pl].astype(np.float64))
            admitted = demand * gate.astype(dtype)
            held = demand - admitted
            arrivals = over_links(admitted)
        arrived = buf + arrivals
        served = np.minimum(arrived, one)
        buf = arrived - served
        serviced_t.append(served)
        backlog_t.append(buf if depth is None else buf + over_links(held))
    serviced = np.asarray(serviced_t, np.float64) * cap
    backlog = np.asarray(backlog_t, np.float64) * cap
    t_drain = (float(serviced.max(axis=1).sum()) + float(backlog[-1].max())) / LINK_BYTES_PER_S
    wait = backlog / LINK_BYTES_PER_S
    qd = np.stack([np.bincount(pf, weights=wait_w[pl], minlength=w.size) for wait_w in wait])
    weight = share[:, phase] * w[None, :]
    mean_queue = float((weight * qd).sum() / weight.sum())
    total = float(w.sum())
    avg_hops = float((w * hops).sum()) / total
    t_sf = total / PACKET_BYTES / max(1, parts) * avg_hops * SIM["hop_latency_s"]
    t_latency = iterations * avg_hops * SIM["hop_latency_s"]
    return {
        "t_network_contended_s": max(t_sf, t_drain) + t_latency + mean_queue,
        "t_drain_s": t_drain,
    }
