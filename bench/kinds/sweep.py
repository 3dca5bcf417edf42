"""The sweep kind of mix: every unit is one whole `run_sweep` answer over the
mix's grid on the jax backend.  A mix with `cache: cold` recomputes
everything in every answer; `cache: warm` reads traces and traffic from a
directory filled in set-up.

A kind (`bench/kinds/<kind>.py`, found by a mix's `kind`) has a `Work`
class that owns set-up (`setup`), one unit (`unit`), and the check of what
the window produced against `bench/reference.py` (`check`), which returns
the numbers compared for each unit, by name.  The program is imported by
the kinds and `control.py` alone; the references never import it.
"""
from __future__ import annotations

import collections
import itertools
import math
import shutil
import tempfile

import numpy as np

from bench import reference as ref

SIM_FIELDS = ref.SIM_FIELDS
ROUTINGS = ("dor", "adaptive2")
# Placements that the program searches, and so claims a local optimum for.
SEARCHED = ("greedy", "quad")
BACKENDS = ("numpy", "jax")


class _Taps:
    """Keeps what the sweep answered that its records leave out: the
    placements `place_batch` returned and the results of every nocsim arm.
    The wrappers pass every argument and result through unchanged."""

    def __init__(self):
        import repro.experiments.sweep as sweep_mod
        import repro.nocsim.batch as noc_mod

        self.sites: list[list[np.ndarray]] = []
        self.noc: list[tuple[str, float | None, str, list[tuple[float, float]]]] = []
        place, contended = sweep_mod.place_batch, noc_mod.contended_batch

        def place_tap(*args, **kw):
            placements, stats = place(*args, **kw)
            self.sites.append([np.asarray(p.site, np.int64).copy() for p in placements])
            return placements, stats

        def contended_tap(*args, **kw):
            out = contended(*args, **kw)
            p = kw["noc_params"]
            self.noc.append((
                p.routing, float(p.buffer_depth) if p.flow_control == "credit" else None,
                out[0].backend if out else "",
                [(r.t_network_contended_s, r.t_drain_s) for r in out],
            ))
            return out

        self._restore = [(sweep_mod, "place_batch", place), (noc_mod, "contended_batch", contended)]
        sweep_mod.place_batch = place_tap
        noc_mod.contended_batch = contended_tap

    def reset(self):
        self.sites, self.noc = [], []

    def close(self):
        for mod, name, fn in self._restore:
            setattr(mod, name, fn)


def _config_key(algorithm, partitioner, placement, topology, parts) -> tuple:
    return (algorithm, partitioner, placement, topology, int(parts))


class Work:
    def __init__(self, config, mix, seed, graph, devices):
        from repro.experiments.grid import GridSpec

        self.config, self.mix, self.graph = config, mix, graph
        self.grid = GridSpec(
            name=config["name"], workloads=(config["name"],), scale=config["scale"], seed=seed,
            **{k: tuple(v) if isinstance(v, list) else v for k, v in mix["grid"].items()},
        )
        self.taps = _Taps()
        self.cache_dir = None
        if mix["cache"] == "warm":
            self.cache_dir = tempfile.mkdtemp(prefix="bench-sweep-cache-")
        elif mix["cache"] != "cold":
            raise ValueError(f"sweep cache must be cold or warm, not {mix['cache']!r}")
        self.info = {"num_nodes": graph.num_nodes, "num_edges": graph.num_edges, "iterations": []}

    def _cache(self):
        from repro.experiments.cache import SweepCache

        traces = self.traces = {}

        class KeepingCache(SweepCache):
            def trace(self, g, algorithm, **kw):
                tr = super().trace(g, algorithm, **kw)
                traces[algorithm] = (np.asarray(tr.props).copy(), int(tr.num_iterations))
                return tr

        return KeepingCache(self.cache_dir)

    def setup(self) -> None:
        from repro.graph.structs import HostGraph

        g = self.graph
        self.host_graph = HostGraph(g.num_nodes, g.src, g.dst, None, g.name)
        if self.cache_dir is not None:
            self.unit()  # fills the cache directory
        self.unit()  # the warm-up answer: every shape the window uses

    def unit(self) -> dict:
        from repro.experiments.sweep import run_sweep

        self.taps.reset()
        sr = run_sweep(
            self.grid, cache=self._cache(), backend="jax", measure_serial=False,
            graphs={self.config["name"]: self.host_graph},
        )
        if len(self.taps.sites) != 1:
            raise RuntimeError(f"run_sweep placed {len(self.taps.sites)} times, not once")
        out = {
            "configs": [r.config for r in sr.records],
            "iterations": [r.num_iterations for r in sr.records],
            "sim": [{f: float(getattr(r.result, f)) for f in SIM_FIELDS} for r in sr.records],
            "sites": self.taps.sites[0],
            "noc": list(self.taps.noc),
            "traces": dict(self.traces),
            "backends": (sr.backend, sr.placement_stats.get("backend", ""),
                         tuple(sr.contention["backends"]) if sr.contention else ()),
        }
        self.info["iterations"].append({c.algorithm: i for c, i in zip(out["configs"], out["iterations"])})
        return out

    def close(self) -> None:
        self.taps.close()
        if self.cache_dir is not None:
            shutil.rmtree(self.cache_dir, ignore_errors=True)

    def expected_configs(self) -> collections.Counter:
        """The configs the mix's grid asks for: every algorithm, scheme (the
        partitioners and placements taken pairwise), topology and size."""
        g = self.mix["grid"]
        schemes = list(zip(g["partitioners"], g["placements"]))
        return collections.Counter(
            _config_key(a, pt, pl, t, p)
            for a, (pt, pl), t, p in itertools.product(g["algorithms"], schemes, g["topologies"], g["parts"]))

    def expected_arms(self) -> list[tuple[str, float | None]]:
        """The nocsim arms a contention grid runs on each backend: per
        routing, the open loop, credit at every buffer depth, and credit
        with unbounded buffers."""
        g = self.mix["grid"]
        if not g.get("contention"):
            return []
        depths = [None] + ([float(d) for d in g["buffer_depths"]] + [math.inf] if g.get("buffer_depths") else [])
        return [(r, d) for r in ROUTINGS for d in depths]

    def check(self, outs: list[dict], seed: int) -> list[dict]:
        """The numbers compared for each answer of the window."""
        g = self.graph
        src, dst, n = g.src, g.dst, g.num_nodes
        for alg in self.grid.algorithms:
            if alg not in ("bfs", "pagerank"):
                raise ValueError(f"no reference for algorithm {alg!r}")
        bfs = ref.bfs(src, dst, n, 0) if "bfs" in self.grid.algorithms else None
        ranks = {}

        def pagerank(iterations):
            """The reference at the program's iteration count, which the
            stopping rule then judges on its own."""
            if iterations not in ranks:
                ranks[iterations] = ref.pagerank(src, dst, n, iterations=iterations)
            return ranks[iterations]

        parts, topos, mats, nets = {}, {}, {}, {}
        want_configs = self.expected_configs()
        want_arms = self.expected_arms()
        checked = []
        for out in outs:
            simulate_b, placement_b, noc_b = out["backends"]
            nums = {
                "jax_path_misses": (simulate_b != "jax") + (placement_b != "jax") + ("jax" not in noc_b),
                "answers_missing": 0, "iterations_mismatch": 0, "pagerank_stop_rel": 0.0,
                "placement_invalid": 0, "placement_swap_gain": 0.0, "simulate_rel": 0.0,
                "nocsim_rel": 0.0, "nocsim_credit_rel": 0.0,
            }
            got_configs = collections.Counter(
                _config_key(c.algorithm, c.partitioner, c.placement, c.topology, c.num_parts)
                for c in out["configs"])
            nums["answers_missing"] += sum(((want_configs - got_configs) + (got_configs - want_configs)).values())
            records = len(out["configs"])
            nums["answers_missing"] += abs(len(out["sites"]) - records) + abs(len(out["sim"]) - records)
            arms = {}
            for routing, depth, backend, res in out["noc"]:
                arms[(routing, depth, backend)] = res
            for routing, depth in want_arms:
                for backend in BACKENDS:
                    res = arms.get((routing, depth, backend))
                    nums["answers_missing"] += records if res is None else abs(len(res) - records)
            if self.mix.get("check_traces"):
                props = np.asarray(out["traces"]["bfs"][0], np.float64)
                nums["bfs_mismatch"] = int((props != bfs[0]).sum())
                got, k = out["traces"]["pagerank"]
                want = pagerank(k)[0]
                nums["pagerank_rel"] = float((np.abs(np.asarray(got, np.float64) - want) / want).max())
            for i, c in enumerate(out["configs"][: min(len(out["sites"]), len(out["sim"]))]):
                iters = out["iterations"][i]
                if c.algorithm == "bfs":
                    _, want_iters, eact, vact = bfs
                    nums["iterations_mismatch"] += int(iters != want_iters)
                else:
                    _, _, eact, vact, deltas = pagerank(iters)
                    nums["pagerank_stop_rel"] = max(nums["pagerank_stop_rel"], ref.stop_violation(
                        deltas, iters, ref.SWEEP_PAGERANK_ITERATIONS))
                pkey = (c.partitioner, c.num_parts)
                if pkey not in parts:
                    parts[pkey] = ref.partition(c.partitioner, src, n, c.num_parts)
                tkey = (c.algorithm, iters) + pkey
                if tkey not in mats:
                    vpart, epart = parts[pkey]
                    mats[tkey] = ref.traffic(vpart, epart, src, eact, vact, c.num_parts)
                m = mats[tkey]
                topo = topos.setdefault((c.topology, c.num_parts), ref.Topology(c.topology, c.num_parts))
                sites = out["sites"][i]
                problems = ref.placement_problems(
                    sites, topo, random_seed=c.seed if c.placement == "random" else None)
                nums["placement_invalid"] += problems
                if problems:
                    continue
                if c.placement in SEARCHED:
                    nums["placement_swap_gain"] = max(nums["placement_swap_gain"], ref.swap_gain(m, sites, topo))
                want = ref.simulate(m, sites, topo, iters, c.num_parts)
                for f in SIM_FIELDS:
                    nums["simulate_rel"] = max(nums["simulate_rel"], _rel(out["sim"][i][f], want[f]))
                for routing, depth in want_arms:
                    nkey = (tkey, c.topology, sites.tobytes(), routing, depth)
                    if nkey not in nets:
                        nets[nkey] = ref.contended(m, sites, topo, iters, c.num_parts, routing=routing, depth=depth)
                    want = nets[nkey]
                    name = "nocsim_credit_rel" if depth is not None and math.isfinite(depth) else "nocsim_rel"
                    for backend in BACKENDS:
                        res = arms.get((routing, depth, backend))
                        if res is None or i >= len(res):
                            continue
                        nums[name] = max(nums[name], _rel(res[i][0], want["t_network_contended_s"]),
                                         _rel(res[i][1], want["t_drain_s"]))
            checked.append(nums)
        return checked


def _rel(got: float, want: float) -> float:
    if got == want:
        return 0.0
    return abs(got - want) / max(abs(want), 1e-300)
