"""Vertex-centric Process → Reduce → Apply engine in JAX (paper Algorithm 1).

This is our GraphMAT equivalent: algorithms are `VertexProgram`s (Table 1
rows); the engine runs full-sweep iterations with masked frontiers, either
jitted (`run`, lax.while_loop) or traced (`run_traced`, Python loop recording
per-edge activity per iteration).  The recorded activity feeds
`repro.core.traffic` exactly like the paper's modified-GraphMAT traces feed
their simulator.

Conventions: vertex arrays carry one sentinel row (index N) so padded edges
are harmless; messages from inactive edges carry the reduce identity.
"""
from __future__ import annotations

import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph.structs import HostGraph, to_device_edges

__all__ = [
    "VertexProgram", "RunResult", "TraceResult", "run", "run_traced", "run_loop", "traced_step",
]

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """One Table 1 row.  All callables are jax-traceable."""

    name: str
    reduce_kind: str  # "min" | "sum" | "max"
    # process(src_prop, edge_weight, aux) -> message along the edge
    process: typing.Callable[[Array, Array, dict], Array]
    # apply(prop, temp, aux) -> new prop
    apply: typing.Callable[[Array, Array, dict], Array]
    # init(num_nodes, source) -> (props, active) both length N+1 (sentinel row)
    init: typing.Callable[[int, int], tuple[Array, Array]]
    # aux(graph) -> dict of precomputed per-vertex arrays (e.g. out-degree)
    make_aux: typing.Callable[[HostGraph], dict] = lambda g: {}
    # frontier semantics: "delta" re-activates changed vertices, "all" keeps
    # every vertex active each iteration (PageRank-style)
    frontier: str = "delta"
    # convergence tolerance for frontier="all" programs
    tol: float = 1e-6

    @property
    def identity(self) -> float:
        return {"min": jnp.inf, "max": -jnp.inf, "sum": 0.0}[self.reduce_kind]

    def segment_reduce(self, data: Array, segment_ids: Array, num_segments: int) -> Array:
        if self.reduce_kind == "min":
            return jax.ops.segment_min(data, segment_ids, num_segments=num_segments)
        if self.reduce_kind == "max":
            return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
        return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


@dataclasses.dataclass
class RunResult:
    props: np.ndarray  # (N,) final vertex properties (sentinel dropped)
    num_iterations: int


@dataclasses.dataclass
class TraceResult:
    props: np.ndarray
    num_iterations: int
    # per-edge count of iterations in which the edge carried a message —
    # the trace the paper's simulator consumes (via traffic_from_partition).
    edge_activity: np.ndarray
    # per-vertex count of iterations in which apply changed the vertex
    vertex_activity: np.ndarray
    # per-iteration frontier sizes (diagnostics)
    frontier_sizes: list[int]


def _one_iteration(
    program: VertexProgram,
    graph: tuple[Array, Array, Array, Array | None],
    props: Array,
    active: Array,
    aux: dict,
) -> tuple[Array, Array, Array]:
    """Returns (new_props, new_active, edge_active).  `graph` is the device
    edge list (src, dst, valid, weight-or-None) of an `EdgeList`."""
    src, dst, valid, weight = graph
    n_sentinel = props.shape[0]  # N + 1
    w = weight if weight is not None else jnp.ones(src.shape[0], jnp.float32)
    edge_active = active[src] & valid
    msg = program.process(props[src], w, aux)
    msg = jnp.where(edge_active, msg, jnp.asarray(program.identity, msg.dtype))
    temp = program.segment_reduce(msg, dst, n_sentinel)
    new_props = program.apply(props, temp, aux)
    new_props = new_props.at[-1].set(props[-1])  # sentinel never changes
    if program.frontier == "delta":
        changed = new_props != props
        new_active = changed.at[-1].set(False)
    else:
        new_active = active
    return new_props, new_active, edge_active


# The graph and `aux` enter both compiled programs as arguments, never as
# closure constants: a baked-in edge list makes the program as large as the
# graph and gives it a compile-cache key that changes with every graph.  One
# executable serves every graph of a given shape.
traced_step = jax.jit(_one_iteration, static_argnums=0)


@functools.partial(jax.jit, static_argnums=(0, 1))
def run_loop(program: VertexProgram, max_iterations: int, graph, props, active, aux):
    """`lax.while_loop` over `_one_iteration` until the frontier empties
    ("delta") or the L1 change drops to `program.tol` ("all")."""

    def cond(state):
        _, active, it, delta = state
        if program.frontier == "delta":
            return jnp.any(active) & (it < max_iterations)
        return (delta > program.tol) & (it < max_iterations)

    def body(state):
        props, active, it, _ = state
        new_props, new_active, _ = _one_iteration(program, graph, props, active, aux)
        delta = jnp.sum(jnp.abs(jnp.nan_to_num(new_props - props, posinf=0.0)))
        return new_props, new_active, it + 1, delta

    return jax.lax.while_loop(
        cond, body, (props, active, jnp.asarray(0), jnp.asarray(jnp.inf, props.dtype))
    )


def _device_graph(g: HostGraph, program: VertexProgram, pad_to: int | None):
    edges = to_device_edges(g, pad_to=pad_to)
    graph = (edges.src, edges.dst, edges.valid, edges.weight)
    aux = {k: jnp.asarray(v) for k, v in program.make_aux(g).items()}
    return graph, aux


def run(
    g: HostGraph,
    program: VertexProgram,
    *,
    source: int = 0,
    max_iterations: int = 10_000,
    pad_to: int | None = None,
) -> RunResult:
    """Jitted execution with lax.while_loop until frontier-empty/converged."""
    graph, aux = _device_graph(g, program, pad_to)
    props0, active0 = program.init(g.num_nodes, source)
    props, _, it, _ = run_loop(program, max_iterations, graph, props0, active0, aux)
    return RunResult(np.asarray(props[:-1]), int(it))


def run_traced(
    g: HostGraph,
    program: VertexProgram,
    *,
    source: int = 0,
    max_iterations: int = 200,
    pad_to: int | None = None,
) -> TraceResult:
    """Python-loop execution that records the communication trace
    (per-edge/vertex activity) for the NoC simulator.

    Inside a span it records, as that span's arguments (`obs.timer` and
    `obs.count`): `wait_ns`, the frontier test and each step until its
    outputs are ready; `host_ns`, the edge upload and per iteration the
    copies to the host and the float64 accumulations; `d2h_bytes`, the
    bytes of every array copied to the host."""
    with obs.timer("host_ns"):
        graph, aux = _device_graph(g, program, pad_to)
        props, active = program.init(g.num_nodes, source)

    e_real = g.num_edges
    edge_activity = np.zeros(e_real, dtype=np.float64)
    vertex_activity = np.zeros(g.num_nodes, dtype=np.float64)
    frontier_sizes: list[int] = []
    it = 0
    while it < max_iterations:
        with obs.timer("wait_ns"):
            if program.frontier == "delta" and not bool(jnp.any(active)):
                break
            new_props, new_active, edge_active = jax.block_until_ready(
                traced_step(program, graph, props, active, aux)
            )
        with obs.timer("host_ns"):
            # one host copy of the step's edge mask serves both uses
            edge_host = np.asarray(edge_active)
            edge_activity += edge_host[:e_real]
            changed = np.asarray(new_props != props)
            vertex_activity += changed[:-1]
            frontier_sizes.append(int(edge_host.sum()))
            diff = np.asarray(new_props - props)
            delta = float(np.nan_to_num(np.abs(diff), posinf=0.0).sum())
            obs.count("d2h_bytes", edge_host.nbytes + changed.nbytes + diff.nbytes)
        props, active = new_props, new_active
        it += 1
        if program.frontier == "all" and delta <= program.tol:
            break
    with obs.timer("host_ns"):
        final = np.asarray(props[:-1])
        obs.count("d2h_bytes", final.nbytes)
    return TraceResult(
        props=final,
        num_iterations=it,
        edge_activity=edge_activity,
        vertex_activity=vertex_activity,
        frontier_sizes=frontier_sizes,
    )
