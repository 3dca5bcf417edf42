"""The least bytes the distributed step moves per chip and per iteration,
from its shapes.

`DistributedEngine`'s jitted `dist_step` (graph/distributed.py) reads each
edge slot of its shard once (int32 `src_slot` and `dst_key`, bool `valid`,
float32 `weight` where the program reads it), reads its vertex state once
(float32 props and bool active over `n_local + 1` slots, the last a
sentinel), sends and receives its `(engines, n_local)` block of partials
through the `all_to_all` (each a read and a write of HBM), and writes its
vertex state once.  The gathers `props[src_slot]` and `active[src_slot]`
read vertex arrays already counted, and the segment reduction's output is
the block that is sent, so neither counts again.
"""
from __future__ import annotations

INT32, FLOAT32, BOOL = 4, 4, 1


def dist_step_bytes(n_local: int, e_local: int, engines: int, weighted: bool,
                    exchange_itemsize: int = FLOAT32) -> int:
    edge_in = e_local * (INT32 + INT32 + BOOL + (FLOAT32 if weighted else 0))
    vertex_in = (n_local + 1) * (FLOAT32 + BOOL)
    exchange = 2 * engines * n_local * exchange_itemsize
    out = (n_local + 1) * (FLOAT32 + BOOL)
    return edge_in + vertex_in + exchange + out
