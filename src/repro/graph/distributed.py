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

The physical device order is permuted by `repro.core.mapping.DeviceMapper` so
heavy shard pairs sit on neighbouring chips — the paper's placement step.
Optional bf16 message compression halves collective bytes (beyond-paper).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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
    """Static-shape device-sharded graph. All (P, ·) arrays sharded on axis 0."""

    num_devices: int
    num_nodes: int
    n_local: int  # owned vertex slots per device (padded)
    e_local: int  # edge slots per device (padded)
    src_slot: jnp.ndarray  # (P, E) local slot of the edge source
    dst_key: jnp.ndarray  # (P, E) dst_part * n_local + dst_slot
    weight: jnp.ndarray  # (P, E) float32
    valid: jnp.ndarray  # (P, E) bool
    slot_to_vertex: np.ndarray  # (P, n_local) host-side inverse map (sentinel -1)

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
            src_slot=jnp.asarray(src_slot),
            dst_key=jnp.asarray(dst_key),
            weight=jnp.asarray(weight),
            valid=jnp.asarray(valid),
            slot_to_vertex=slot_to_vertex,
        )


class DistributedEngine:
    """Runs a VertexProgram over a ShardedVertexGraph on an 'engines' mesh."""

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

    def _shard(self, sg: ShardedVertexGraph) -> ShardedVertexGraph:
        spec = NamedSharding(self.mesh, P("engines"))
        return dataclasses.replace(
            sg,
            src_slot=jax.device_put(sg.src_slot, spec),
            dst_key=jax.device_put(sg.dst_key, spec),
            weight=jax.device_put(sg.weight, spec),
            valid=jax.device_put(sg.valid, spec),
        )

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
        spec = NamedSharding(self.mesh, P("engines"))
        return jax.device_put(jnp.asarray(props), spec), jax.device_put(jnp.asarray(active), spec)

    def step_fn(self, sg: ShardedVertexGraph):
        prog = self.program
        Pn, n_local = sg.num_devices, sg.n_local
        identity = prog.identity

        def local_step(props, active, src_slot, dst_key, weight, valid, aux):
            # leading device axis of size 1 inside shard_map → squeeze
            props, active = props[0], active[0]
            src_slot, dst_key = src_slot[0], dst_key[0]
            weight, valid = weight[0], valid[0]
            msg_active = active[src_slot] & valid
            msg = prog.process(props[src_slot], weight, aux)
            msg = jnp.where(msg_active, msg, jnp.asarray(identity, msg.dtype))
            # per-destination-device partial reduce: (P * n_local,) (+1 sentinel)
            partial = prog.segment_reduce(msg, dst_key, Pn * n_local + 1)[:-1]
            partial = partial.reshape(Pn, n_local)
            if self.comm_dtype is not None:
                partial = partial.astype(self.comm_dtype)
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
            local_step, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(mapped)

    def run(
        self,
        g: HostGraph,
        partition: Partition,
        *,
        source: int = 0,
        max_iterations: int = 200,
    ):
        sg = ShardedVertexGraph.build(g, partition)
        sg = self._shard(sg)
        aux_np = self.program.make_aux(g)
        # per-vertex aux arrays are not supported in the distributed engine;
        # PR folds 1/outdeg into edge weights (algorithms.prepare_graph).
        aux = {k: jnp.asarray(v) for k, v in aux_np.items() if np.ndim(v) == 0}
        props, active = self.init_state(sg, source)
        step = self.step_fn(sg)
        it = 0
        while it < max_iterations:
            if self.program.frontier == "delta" and not bool(jnp.any(active[:, :-1])):
                break
            props, active, delta = step(
                props, active, sg.src_slot, sg.dst_key, sg.weight, sg.valid, aux
            )
            it += 1
            if self.program.frontier == "all" and float(delta) <= self.program.tol:
                break
        # gather to host order
        out = np.full(g.num_nodes, np.nan, np.float32)
        host = np.asarray(props)[:, :-1]
        ok = sg.slot_to_vertex >= 0
        out[sg.slot_to_vertex[ok]] = host[ok]
        return out, it
