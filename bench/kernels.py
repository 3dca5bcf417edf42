"""The least bytes a kernel must move per call, from its shapes.

`traced_step` (graph/vertex_program.py, the jitted BSP iteration) reads each
edge array once (int32 src and dst, bool valid, float32 weight where the
graph is weighted), reads the vertex arrays once (float32 props and bool
active over |V| + 1 slots, the last a sentinel), and writes its outputs once
(float32 props and bool active over |V| + 1, bool edge activity over |E|).
The gathers props[src] and active[src] read vertex arrays that are already
counted, and the segment reduction's output is an intermediate, so neither
counts again.
"""
from __future__ import annotations

INT32, FLOAT32, BOOL = 4, 4, 1


def traced_step_bytes(num_nodes: int, num_edges: int, weighted: bool) -> int:
    edge_in = num_edges * (INT32 + INT32 + BOOL + (FLOAT32 if weighted else 0))
    vertex_in = (num_nodes + 1) * (FLOAT32 + BOOL)
    out = (num_nodes + 1) * (FLOAT32 + BOOL) + num_edges * BOOL
    return edge_in + vertex_in + out
