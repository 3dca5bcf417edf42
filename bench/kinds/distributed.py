"""The distributed kind of mix: a graph kept resident on an `engines` mesh,
one engine per chip, answering query after query.  Set-up partitions the
graph (the mix's `partitioner`), places the shards on the chips with
`DeviceMapper` (Algorithm 4 over the mix's `mesh`) and loads it once for
each query program with `DistributedEngine.load`.  Every unit is one BFS
from the next root of out-degree >= 1, drawn from the seed, then one
PageRank with the sweep's tolerance and iteration cap, each through
`DistributedEngine.run`.

The check compares every answer of the window with `bench/reference.py`
(float64, importing nothing of the program): BFS levels and iterations
exactly, PageRank at the program's iteration count and where it stopped.
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference as ref

QUERIES = ("bfs", "pagerank")
PLACEMENTS = ("device_mapper",)


def max_iterations() -> dict:
    """The sweep's iteration caps, per query, which the units keep."""
    from repro.experiments.sweep import DEFAULT_TRACE_ITERS, TRACE_ITERS

    return {name: TRACE_ITERS.get(name, DEFAULT_TRACE_ITERS) for name in QUERIES}


class Work:
    def __init__(self, config, mix, seed, graph, devices):
        self.mix, self.graph, self.devices = mix, graph, list(devices)
        if tuple(mix["queries"]) != QUERIES:
            raise ValueError(f"distributed queries must be {list(QUERIES)}, not {mix['queries']!r}")
        if mix["placement"] not in PLACEMENTS:
            raise ValueError(f"distributed placement must be one of {PLACEMENTS}, not {mix['placement']!r}")
        engines = math.prod(mix["mesh"])
        if engines != len(self.devices):
            raise ValueError(f"mesh {mix['mesh']} holds {engines} engines, one a device; "
                             f"the cell has {len(self.devices)} devices")
        # the exchange's dtype; control scripts set it before set-up
        self.comm_dtype = mix["comm_dtype"]
        out_degree = np.bincount(graph.src, minlength=graph.num_nodes)
        self.candidates = np.flatnonzero(out_degree >= 1)
        self.rng = np.random.default_rng(seed)
        self.max_iterations = max_iterations()
        self.info = {"num_nodes": graph.num_nodes, "num_edges": graph.num_edges, "engines": engines,
                     "roots": [], "iterations": []}

    def setup(self) -> None:
        import jax.numpy as jnp

        from repro.core.mapping import DeviceMapper
        from repro.graph.algorithms import bfs_program, pagerank_program, prepare_graph
        from repro.graph.distributed import DistributedEngine, make_engines_mesh
        from repro.graph.structs import HostGraph

        g = self.graph
        host = HostGraph(g.num_nodes, g.src, g.dst, None, g.name)
        perm, part, _, _ = DeviceMapper(tuple(self.mix["mesh"])).device_permutation(
            host.src, host.dst, host.num_nodes, partitioner=self.mix["partitioner"])
        mesh = make_engines_mesh(perm, self.devices)
        comm = None if self.comm_dtype == "float32" else jnp.dtype(self.comm_dtype)
        self.engines = {
            "bfs": DistributedEngine(bfs_program(), mesh, comm_dtype=comm),
            "pagerank": DistributedEngine(pagerank_program(), mesh, comm_dtype=comm),
        }
        self.loaded = {
            "bfs": self.engines["bfs"].load(host, part),
            "pagerank": self.engines["pagerank"].load(prepare_graph("pagerank", host), part),
        }
        sg = self.loaded["bfs"]
        self.info.update(n_local=sg.n_local, e_local=sg.e_local,
                         exchange_itemsize=self.engines["bfs"].exchange_itemsize)
        self.unit()  # the warm-up unit: both steps compile here

    def unit(self) -> dict:
        root = int(self.rng.choice(self.candidates))
        out = {"root": root}
        for name in QUERIES:
            props, iterations = self.engines[name].run(
                self.loaded[name], source=root, max_iterations=self.max_iterations[name])
            out[name] = (props, iterations)
        self.info["roots"].append(root)
        self.info["iterations"].append({name: out[name][1] for name in QUERIES})
        return out

    def close(self) -> None:
        self.engines = self.loaded = None

    def check(self, outs: list[dict], seed: int) -> list[dict]:
        """The numbers compared for each unit of the window."""
        g = self.graph
        src, dst, n = g.src, g.dst, g.num_nodes
        cap = self.max_iterations["pagerank"]
        ranks, levels = {}, {}
        checked = []
        for out in outs:
            nums = {"answers_missing": 0, "bfs_mismatch": 0, "iterations_mismatch": 0,
                    "pagerank_rel": 0.0, "pagerank_stop_rel": 0.0}
            root = out["root"]
            if root not in levels:
                levels[root] = ref.bfs(src, dst, n, root, max_iterations=self.max_iterations["bfs"])[:2]
            want_levels, want_iterations = levels[root]
            got, iterations = _answer(out.get("bfs"), n)
            if got is None:
                nums["answers_missing"] += 1
            else:
                nums["answers_missing"] += int(np.isnan(got).sum())
                nums["bfs_mismatch"] = int((got != want_levels).sum())
                nums["iterations_mismatch"] = int(iterations != want_iterations)
            got, iterations = _answer(out.get("pagerank"), n)
            if got is None:
                nums["answers_missing"] += 1
            else:
                if iterations not in ranks:
                    ranks[iterations] = ref.pagerank(src, dst, n, iterations=iterations)
                want, _, _, _, deltas = ranks[iterations]
                answered = ~np.isnan(got)
                nums["answers_missing"] += int((~answered).sum())
                if answered.any():
                    nums["pagerank_rel"] = float((np.abs(got - want) / want)[answered].max())
                nums["pagerank_stop_rel"] = ref.stop_violation(deltas, iterations, cap)
            checked.append(nums)
        return checked


def _answer(answer, n: int):
    """(props as float64, iterations), or (None, None) where the query gave
    no answer of the graph's length."""
    if answer is None:
        return None, None
    props, iterations = answer
    props = np.asarray(props, np.float64)
    if props.shape != (n,) or iterations < 1:
        return None, None
    return props, int(iterations)
