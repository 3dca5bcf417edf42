"""Sort-based reference for one block's COO traffic: each of the four
Process/Reduce flows (and the Apply flow) built as a per-edge flat-key array
and reduced by `np.unique` through its own accumulator — the form the shard
payloads were first written in.  `core.traffic`'s histogram blocks must
match it byte for byte."""
from __future__ import annotations

import numpy as np

from repro.core.traffic import EPROP, ET, VPROP, VTEMP


class _SortAccumulator:
    def __init__(self) -> None:
        self.keys = np.empty(0, dtype=np.int64)
        self.vals = np.empty(0, dtype=np.float64)

    def add(self, flat: np.ndarray, w: np.ndarray) -> None:
        if flat.size == 0:
            return
        keys, inv = np.unique(flat, return_inverse=True)
        sums = np.bincount(inv, weights=w, minlength=keys.size)
        merged = np.concatenate([self.keys, keys])
        merged_vals = np.concatenate([self.vals, sums])
        self.keys, inv2 = np.unique(merged, return_inverse=True)
        self.vals = np.bincount(inv2, weights=merged_vals, minlength=self.keys.size)


def edge_block_oracle(partition, src, dst, *, edge_activity, packet_bytes, model, lo, hi):
    P = partition.num_parts
    n = 4 * P
    src = np.asarray(src, dtype=np.int64)[lo:hi]
    dst = np.asarray(dst, dtype=np.int64)[lo:hi]
    if edge_activity is None:
        w = np.full(src.size, float(packet_bytes), dtype=np.float64)
    else:
        w = np.asarray(edge_activity[lo:hi], dtype=np.float64) * packet_bytes
    ep = partition.edge_part[lo:hi].astype(np.int64)
    sp = partition.vertex_part[src].astype(np.int64)
    dp = partition.vertex_part[dst].astype(np.int64)
    et = ET * P + ep
    eprop = EPROP * P + ep
    vprop = VPROP * P + sp
    vtemp = VTEMP * P + (ep if model == "paper" else dp)
    acc = _SortAccumulator()
    acc.add(et * n + vprop, w)
    acc.add(vprop * n + eprop, w)
    acc.add(eprop * n + vtemp, w)
    acc.add(et * n + vtemp, w)
    return acc.keys, acc.vals, float(w.sum())


def vertex_block_oracle(partition, *, vertex_activity, packet_bytes, lo, hi):
    P = partition.num_parts
    n = 4 * P
    if vertex_activity is None:
        wv = np.full(hi - lo, float(packet_bytes), dtype=np.float64)
    else:
        wv = np.asarray(vertex_activity[lo:hi], dtype=np.float64) * packet_bytes
    vp = partition.vertex_part[lo:hi].astype(np.int64)
    acc = _SortAccumulator()
    acc.add((VTEMP * P + vp) * n + (VPROP * P + vp), wv)
    return acc.keys, acc.vals, float(wv.sum())
