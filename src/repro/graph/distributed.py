"""Distributed vertex-centric execution under shard_map (DESIGN.md Level B).

The pod's devices are the engines.  Vertices are dealt to devices by the
paper's Algorithm 2 (degree-sorted cyclic); edges are source-cut, so Process
reads are device-local by construction — exactly the property the paper's
partitioning buys.  Reduce delivery is a combiner-style exchange: each device
segment-reduces its outgoing messages *per destination device* into a
(P, n_local) partial block and a single all_to_all delivers every partial to
its owner (bytes per device = P·n_local·itemsize, independent of edge count —
the TPU-idiomatic replacement for per-packet NoC routing; see DESIGN.md
hardware-adaptation notes).

A deployment keeps its graph resident and answers query after query:
`DistributedEngine.load` builds and places the sharded graph once, and
`DistributedEngine.run` answers one query on it with the step the engine
compiled once.

The physical device order is permuted by `repro.core.mapping.DeviceMapper` so
heavy shard pairs sit on neighbouring chips — the paper's placement step.
Optional bf16 message compression halves collective bytes (beyond-paper).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.partition import Partition
from repro.graph.structs import HostGraph
from repro.graph.vertex_program import VertexProgram

__all__ = ["ShardedVertexGraph", "DistributedEngine", "make_engines_mesh"]


def make_engines_mesh(site_permutation: np.ndarray | None = None, devices=None) -> Mesh:
    """1-D 'engines' mesh; `site_permutation[p]` = physical device for shard p."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    if site_permutation is not None:
        devices = devices[np.asarray(site_permutation)]
    return Mesh(devices, ("engines",))


@dataclasses.dataclass
class ShardedVertexGraph:
    """Static-shape sharded graph.  `build` makes the (P, ·) arrays on the
    host; `DistributedEngine.load` places them on the mesh, sharded on axis
    0, with the program's scalar `aux` beside them."""

    num_devices: int
    num_nodes: int
    n_local: int  # owned vertex slots per device (padded)
    e_local: int  # edge slots per device (padded)
    src_slot: np.ndarray | jax.Array  # (P, E) local slot of the edge source
    dst_key: np.ndarray | jax.Array  # (P, E) dst_part * n_local + dst_slot
    weight: np.ndarray | jax.Array  # (P, E) float32
    valid: np.ndarray | jax.Array  # (P, E) bool
    slot_to_vertex: np.ndarray  # (P, n_local) host-side inverse map (sentinel -1)
    aux: dict = dataclasses.field(default_factory=dict)  # scalar program aux, once loaded

    @staticmethod
    def build(g: HostGraph, partition: Partition) -> "ShardedVertexGraph":
        Pn = partition.num_parts
        n = g.num_nodes
        # slot(v) = rank of v inside its part, in sorted-order (cyclic deal ⇒
        # slot = position // P for the powerlaw partitioner; computed generically
        # here so random/range/hash partitions work too).
        pos = np.empty(n, dtype=np.int64)
        pos[partition.order] = np.arange(n)
        order_in_part = np.lexsort((pos, partition.vertex_part))
        slot = np.empty(n, dtype=np.int64)
        counts = np.bincount(partition.vertex_part, minlength=Pn)
        n_local = int(counts.max())
        offs = np.concatenate([[0], np.cumsum(counts)[:-1]])
        slot[order_in_part] = np.arange(n) - np.repeat(offs, counts)
        vpart = partition.vertex_part.astype(np.int64)

        slot_to_vertex = np.full((Pn, n_local), -1, dtype=np.int64)
        slot_to_vertex[vpart, slot] = np.arange(n)

        # Edges grouped by their (source-cut) part.
        epart = partition.edge_part.astype(np.int64)
        ecounts = np.bincount(epart, minlength=Pn)
        e_local = int(ecounts.max()) if ecounts.size else 1
        eorder = np.argsort(epart, kind="stable")
        eoffs = np.concatenate([[0], np.cumsum(ecounts)[:-1]])
        row = np.repeat(np.arange(Pn), ecounts)
        col = np.arange(g.num_edges) - np.repeat(eoffs, ecounts)

        src_slot = np.zeros((Pn, e_local), dtype=np.int32)
        dst_key = np.full((Pn, e_local), Pn * n_local, dtype=np.int32)  # sentinel key
        weight = np.zeros((Pn, e_local), dtype=np.float32)
        valid = np.zeros((Pn, e_local), dtype=bool)
        es, ed = g.src[eorder], g.dst[eorder]
        # spilled edges may have src owned remotely; engine still holds a copy
        # of the source property refreshed via the same exchange — for the
        # (rare) spilled edges we fall back to slot of src on *this* device if
        # local, else mark invalid and count them (they are re-homed below).
        src_local_ok = vpart[es] == row
        # re-home any edge whose src is not local to its assigned part (only
        # possible via capacity spill): move it to the src's own part.
        bad = ~src_local_ok
        if bad.any():
            row = np.where(bad, vpart[es], row)
            # recompute packing after re-homing
            order2 = np.argsort(row, kind="stable")
            row, es, ed = row[order2], es[order2], ed[order2]
            w_src = None if g.weight is None else g.weight[eorder][order2]
            ecounts = np.bincount(row, minlength=Pn)
            e_local = int(ecounts.max())
            eoffs = np.concatenate([[0], np.cumsum(ecounts)[:-1]])
            col = np.arange(g.num_edges) - np.repeat(eoffs, ecounts)
            src_slot = np.zeros((Pn, e_local), dtype=np.int32)
            dst_key = np.full((Pn, e_local), Pn * n_local, dtype=np.int32)
            weight = np.zeros((Pn, e_local), dtype=np.float32)
            valid = np.zeros((Pn, e_local), dtype=bool)
        else:
            w_src = None if g.weight is None else g.weight[eorder]

        src_slot[row, col] = slot[es]
        dst_key[row, col] = (vpart[ed] * n_local + slot[ed]).astype(np.int32)
        weight[row, col] = 1.0 if w_src is None else w_src
        valid[row, col] = True

        return ShardedVertexGraph(
            num_devices=Pn,
            num_nodes=n,
            n_local=n_local,
            e_local=e_local,
            src_slot=src_slot,
            dst_key=dst_key,
            weight=weight,
            valid=valid,
            slot_to_vertex=slot_to_vertex,
        )


class DistributedEngine:
    """Runs a VertexProgram on an 'engines' mesh over a graph kept resident
    there: `load` once per graph, then `run` once per query.

    The jitted `shard_map` step is made once per engine.  It reads the
    number of engines from the mesh and the slots per engine from the
    shapes of its arguments, so one executable serves every query of a
    loaded graph, and JAX's shape cache is the only cache.

    Inside an obs span, `load` records `host_ns` (the build and `aux`) and
    `h2d_bytes` (the bytes placed on the mesh) on `dist.load`; each `run`
    records on `dist.run`: `wait_ns`, the frontier test and each step until
    its outputs are ready; `host_ns`, `init_state` and the gather to host
    order; `dispatches`, the steps launched; `iterations`; `exchange_bytes`,
    what each device sends through the `all_to_all` (iterations × engines ×
    n_local × the exchange dtype's itemsize).
    """

    def __init__(
        self,
        program: VertexProgram,
        mesh: Mesh,
        *,
        comm_dtype: jnp.dtype | None = None,
    ):
        self.program = program
        self.mesh = mesh
        self.comm_dtype = comm_dtype  # e.g. jnp.bfloat16 → compressed exchange
        self.exchange_itemsize = np.dtype(np.float32 if comm_dtype is None else comm_dtype).itemsize
        self._sharded = NamedSharding(mesh, P("engines"))
        self._replicated = NamedSharding(mesh, P())
        self._step = self._make_step()

    def load(self, g: HostGraph, partition: Partition) -> ShardedVertexGraph:
        """Build the sharded graph, place it on the mesh and compute the
        program's scalar `aux`, once; the handle serves every `run`."""
        with obs.span("dist.load"):
            with obs.timer("host_ns"):
                sg = ShardedVertexGraph.build(g, partition)
                # per-vertex aux arrays are not supported in the distributed
                # engine; PR folds 1/outdeg into edge weights
                # (algorithms.prepare_graph).
                aux = {k: np.asarray(v) for k, v in self.program.make_aux(g).items() if np.ndim(v) == 0}
            fields = ("src_slot", "dst_key", "weight", "valid")
            host = [getattr(sg, f) for f in fields] + [aux[k] for k in sorted(aux)]
            obs.count("h2d_bytes", sum(a.nbytes for a in host))
            placed = {f: jax.device_put(getattr(sg, f), self._sharded) for f in fields}
            aux = {k: jax.device_put(v, self._replicated) for k, v in aux.items()}
            jax.block_until_ready((placed, aux))
        return dataclasses.replace(sg, aux=aux, **placed)

    def init_state(self, sg: ShardedVertexGraph, source: int = 0):
        """(props, active) as (P, n_local+1) arrays (one sentinel slot each)."""
        prog = self.program
        props_g, active_g = prog.init(sg.num_nodes, source)  # (N+1,) host-side
        props = np.full((sg.num_devices, sg.n_local + 1), props_g[-1], np.float32)
        active = np.zeros((sg.num_devices, sg.n_local + 1), bool)
        s2v = sg.slot_to_vertex
        ok = s2v >= 0
        props[:, :-1][ok] = np.asarray(props_g)[s2v[ok]]
        active[:, :-1][ok] = np.asarray(active_g)[s2v[ok]]
        return jax.device_put(props, self._sharded), jax.device_put(active, self._sharded)

    def _make_step(self):
        prog = self.program
        comm_dtype = self.comm_dtype
        Pn = self.mesh.shape["engines"]
        identity = prog.identity

        def dist_step(props, active, src_slot, dst_key, weight, valid, aux):
            # leading device axis of size 1 inside shard_map → squeeze
            props, active = props[0], active[0]
            src_slot, dst_key = src_slot[0], dst_key[0]
            weight, valid = weight[0], valid[0]
            n_local = props.shape[0] - 1
            msg_active = active[src_slot] & valid
            msg = prog.process(props[src_slot], weight, aux)
            msg = jnp.where(msg_active, msg, jnp.asarray(identity, msg.dtype))
            # per-destination-device partial reduce: (P * n_local,) (+1 sentinel)
            partial = prog.segment_reduce(msg, dst_key, Pn * n_local + 1)[:-1]
            partial = partial.reshape(Pn, n_local)
            if comm_dtype is not None:
                partial = partial.astype(comm_dtype)
            # deliver: device i's row j goes to device j (combiner exchange)
            received = jax.lax.all_to_all(
                partial, "engines", split_axis=0, concat_axis=0, tiled=False
            ).astype(jnp.float32)
            # fold partials from all source devices
            if prog.reduce_kind == "min":
                temp = received.min(axis=0)
            elif prog.reduce_kind == "max":
                temp = received.max(axis=0)
            else:
                temp = received.sum(axis=0)
            temp = jnp.concatenate([temp, jnp.asarray([identity], jnp.float32)])
            new_props = prog.apply(props, temp, aux)
            new_props = new_props.at[-1].set(props[-1])
            if prog.frontier == "delta":
                new_active = (new_props != props).at[-1].set(False)
            else:
                new_active = active
            delta = jnp.sum(jnp.abs(jnp.nan_to_num(new_props - props, posinf=0.0)))
            delta = jax.lax.psum(delta, "engines")
            return new_props[None], new_active[None], delta

        in_specs = (
            P("engines"), P("engines"), P("engines"), P("engines"),
            P("engines"), P("engines"), P(),
        )
        out_specs = (P("engines"), P("engines"), P())
        mapped = jax.shard_map(
            dist_step, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(mapped)

    def run(self, sg: ShardedVertexGraph, *, source: int = 0, max_iterations: int = 200):
        """One query on a loaded graph: (props in host order, iterations)."""
        prog = self.program
        with obs.span("dist.run"):
            with obs.timer("host_ns"):
                props, active = self.init_state(sg, source)
            args = (sg.src_slot, sg.dst_key, sg.weight, sg.valid, sg.aux)
            it = 0
            while it < max_iterations:
                with obs.timer("wait_ns"):
                    if prog.frontier == "delta" and not bool(jnp.any(active[:, :-1])):
                        break
                    props, active, delta = jax.block_until_ready(self._step(props, active, *args))
                    obs.count("dispatches")
                    it += 1
                    if prog.frontier == "all" and float(delta) <= prog.tol:
                        break
            obs.count("iterations", it)
            obs.count("exchange_bytes", it * sg.num_devices * sg.n_local * self.exchange_itemsize)
            with obs.timer("host_ns"):
                # gather to host order
                out = np.full(sg.num_nodes, np.nan, np.float32)
                host = np.asarray(props)[:, :-1]
                ok = sg.slot_to_vertex >= 0
                out[sg.slot_to_vertex[ok]] = host[ok]
        return out, it
