#!/usr/bin/env python3
"""Bring-up check: the sweep's jax path end to end on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # DistributedEngine on four chips

One chip: soc-pokec at scale 0.25 (400,000 vertices, 7.65M edges, the
largest size `--grid scale` runs), generated from seed 0, goes through
`run_sweep` with `backend="jax"` and no disk cache — vertex-program traces
of pagerank and bfs on the device, powerlaw+greedy (the stacked greedy
construction and descent) against random+random on mesh2d and torus2d at
16 engines, `simulate_batch`, and the open, credit and infinite-credit
nocsim arms — and then the degraded nocsim arm at one fault rate.  Every
result is checked against the float64 references under the contracts the
repo states; any miss raises and the process exits non-zero.

Four chips: `DistributedEngine` runs pagerank and bfs on the same graph
under the powerlaw partition, engines permuted by `DeviceMapper((2, 2))`,
and is checked against `reference_pagerank` and `reference_bfs`.

This process is the only one that touches JAX.  Every phase prints its wall
time and the device on an earlier line, taken after the results reached
the host.  The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`,
printed only when every check held; without a TPU the script exits
non-zero before any work.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

WORKLOAD = "soc-pokec"
SCALE = 0.25
SEED = 0
PARTS = 16
FAULT_RATE = 0.05
# The contracts the repo states for each check (sources named beside each).
PAGERANK_ATOL = 1e-4  # tests/test_graph_algorithms.py
SIMULATE_RTOL = 1e-6  # simulate_batch parity gate (repro.experiments.batched)
DESCENT_H_RTOL = 1e-3  # tests/test_placement_batch.py, jax vs numpy descent H


class CheckFailed(RuntimeError):
    """A result missed the contract it is held to."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"[check] ok: {what}", flush=True)


class Phases:
    """Per-phase wall times, each line naming the device."""

    def __init__(self, device: str):
        self.device = device

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        print(f"[time] {name}: {time.perf_counter() - t0:.3f} s on {self.device}", flush=True)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def _keeping_cache():
    """A `SweepCache` with no disk that also keeps what the sweep traced,
    partitioned and built, so the checks test exactly the sweep's inputs."""
    from repro.experiments.cache import SweepCache

    class KeepingCache(SweepCache):
        def __init__(self):
            super().__init__(None)
            self.traces, self.partitions, self.traffics = {}, {}, []

        def trace(self, g, algorithm, **kw):
            tr = self.traces[algorithm] = super().trace(g, algorithm, **kw)
            return tr

        def partition(self, g, partitioner, num_parts, **kw):
            p = self.partitions[(partitioner, num_parts)] = super().partition(
                g, partitioner, num_parts, **kw
            )
            return p

        def traffic(self, *args, **kw):
            t = super().traffic(*args, **kw)
            self.traffics.append(t)
            return t

    return KeepingCache()


def run_one_chip(scale: float, phase: Phases) -> None:
    from repro.core.placement import Placement, auto_mesh_for_parts
    from repro.experiments.grid import GridSpec
    from repro.experiments.placement_batch import (
        batch_descend,
        greedy_construct_batch,
        place_batch,
    )
    from repro.experiments.batched import simulate_batch
    from repro.experiments.resilience import fault_seed
    from repro.experiments.sweep import run_sweep
    from repro.faults.degraded import degraded_batch
    from repro.faults.model import sample_link_faults
    from repro.graph.algorithms import prepare_graph, reference_bfs, reference_pagerank
    from repro.graph.generators import table2_workloads
    from repro.nocsim.batch import PARITY_RTOL

    with phase("data"):
        g = table2_workloads(scale=scale, seed=SEED, names=(WORKLOAD,))[WORKLOAD]
    print(f"[data] {WORKLOAD}@{scale:g}: |V|={g.num_nodes} |E|={g.num_edges}", flush=True)

    grid = GridSpec(
        name="chip_smoke",
        workloads=(WORKLOAD,),
        algorithms=("pagerank", "bfs"),
        partitioners=("powerlaw", "random"),
        placements=("greedy", "random"),
        topologies=("mesh2d", "torus2d"),
        parts=(PARTS,),
        scale=scale,
        seed=SEED,
        contention=True,
        buffer_depths=(0.5, 4.0),
        traffic_edge_block=1 << 20,
    )
    cache = _keeping_cache()
    with phase("sweep"):
        sweep = run_sweep(
            grid,
            cache=cache,
            backend="jax",
            measure_serial=False,
            graphs={WORKLOAD: g},
            progress=lambda msg: print(msg, flush=True),
        )
    for stage in ("trace", "partition_traffic", "placement", "batched_eval", "contention"):
        print(f"[time] sweep.{stage}: {sweep.timings[stage + '_s']:.3f} s on {phase.device}")
    print(
        f"[backend] simulate={sweep.backend} placement={sweep.placement_stats['backend']}"
        f" nocsim={'+'.join(sweep.contention['backends'])}",
        flush=True,
    )
    require(sweep.backend == "jax", "simulate_batch resolved to the jax backend")
    require(sweep.placement_stats["backend"] == "jax", "placement search resolved to jax")
    require("jax" in sweep.contention["backends"], "nocsim arms ran on jax")

    with phase("references"):
        bfs_ref = reference_bfs(prepare_graph("bfs", g), 0)
        pr_ref = reference_pagerank(prepare_graph("pagerank", g))
    bfs_props = np.asarray(cache.traces["bfs"].props, np.float64)
    require(np.array_equal(bfs_props, bfs_ref), "bfs props equal reference_bfs exactly")
    pr_err = np.abs(np.asarray(cache.traces["pagerank"].props, np.float64) - pr_ref)
    print(f"[parity] pagerank max |err| {pr_err.max():.3e}, max rel {(pr_err / pr_ref).max():.3e}")
    require(pr_err.max() <= PAGERANK_ATOL, f"pagerank within atol {PAGERANK_ATOL:g}")

    configs = grid.expand()
    traffics = cache.traffics
    parts = [cache.partitions[(c.partitioner, c.num_parts)] for c in configs]
    topologies = [auto_mesh_for_parts(c.num_parts, c.topology) for c in configs]
    iters = np.array([r.num_iterations for r in sweep.records])
    require(len(traffics) == len(configs), "one traffic matrix per config")

    with phase("simulate parity"):
        placements, _ = place_batch(
            traffics, parts, topologies,
            methods=[c.placement for c in configs], seeds=[c.seed for c in configs],
            backend="jax",
        )
        got = simulate_batch(traffics, placements, num_iterations=iters, backend="jax")
        want = simulate_batch(traffics, placements, num_iterations=iters, backend="numpy")
    # The simulated fields; the contended ones stay None without a NoC replay.
    fields = [
        f.name
        for f in dataclasses.fields(want[0])
        if isinstance(getattr(want[0], f.name), float)
    ]
    sim_rel = max(
        _rel(getattr(a, f), getattr(b, f)) for a, b in zip(got, want) for f in fields
    )
    sweep_rel = max(
        _rel(getattr(r.result, f), getattr(b, f))
        for r, b in zip(sweep.records, want)
        for f in fields
    )
    print(f"[parity] simulate_batch jax vs numpy max rel {sim_rel:.3e}; sweep records {sweep_rel:.3e}")
    require(sim_rel <= SIMULATE_RTOL, f"every simulate_batch field within {SIMULATE_RTOL:g}")
    require(sweep_rel <= SIMULATE_RTOL, f"sweep records within {SIMULATE_RTOL:g} of numpy")

    searched = [i for i, c in enumerate(configs) if c.placement == "greedy"]
    ws = [traffics[i].bytes_matrix for i in searched]
    topos = [topologies[i] for i in searched]
    with phase("descent parity"):
        inits, cons_backend = greedy_construct_batch(
            ws, topos, seeds=[configs[i].seed for i in searched], backend="jax"
        )
        out_jx, st_jx = batch_descend(ws, topos, inits, backend="jax")
        out_np, st_np = batch_descend(ws, topos, inits, backend="numpy")
    require(cons_backend == "jax" and st_jx.backend == "jax", "greedy construction and descent on jax")
    h_rel = max(
        _rel(Placement(t, sj, "x").weighted_hops(w), Placement(t, sn, "x").weighted_hops(w))
        for w, t, sj, sn in zip(ws, topos, out_jx, out_np)
    )
    print(f"[parity] descent H jax vs numpy max rel {h_rel:.3e} (steps {st_jx.steps} vs {st_np.steps})")
    require(h_rel <= DESCENT_H_RTOL, f"converged descent H within {DESCENT_H_RTOL:g}")

    cont = sweep.contention
    print(
        f"[parity] nocsim numpy↔jax {cont['backend_parity_max_rel']:.3e},"
        f" inf-credit numpy |Δ| {cont['credit_inf_numpy_max_abs']:.3e},"
        f" inf-credit jax {cont['credit_inf_jax_max_rel']:.3e}"
    )
    require(cont["backend_parity_max_rel"] <= PARITY_RTOL, f"nocsim parity within {PARITY_RTOL:g}")
    require(cont["credit_inf_numpy_max_abs"] == 0.0, "infinite credit reproduces open loop on numpy")
    require(cont["credit_inf_jax_max_rel"] <= PARITY_RTOL, "infinite credit within parity on jax")

    faultsets = [
        sample_link_faults(
            topo, FAULT_RATE, seed=fault_seed(WORKLOAD, c.topology, c.num_parts, FAULT_RATE)
        )
        for c, topo in zip(configs, topologies)
    ]
    with phase("degraded arm"):
        deg_np = degraded_batch(
            traffics, placements, faultsets, num_iterations=iters, backend="numpy"
        )
        deg_jx = degraded_batch(
            traffics, placements, faultsets, num_iterations=iters, backend="jax"
        )
    deg_rel = max(
        _rel(j.t_network_contended_s, n.t_network_contended_s) for j, n in zip(deg_jx, deg_np)
    )
    dead = sum(f.num_dead_links() for f in faultsets)
    print(f"[parity] degraded arm ({FAULT_RATE:g} link faults, {dead} dead links) {deg_rel:.3e}")
    require(dead > 0, "the degraded fabric has dead links")
    require(deg_rel <= PARITY_RTOL, f"degraded arm parity within {PARITY_RTOL:g}")


def run_four_chips(scale: float, phase: Phases, devices) -> None:
    from repro.core.mapping import DeviceMapper
    from repro.graph.algorithms import (
        bfs_program,
        pagerank_program,
        prepare_graph,
        reference_bfs,
        reference_pagerank,
    )
    from repro.graph.distributed import DistributedEngine, make_engines_mesh
    from repro.graph.generators import table2_workloads

    with phase("data"):
        g = table2_workloads(scale=scale, seed=SEED, names=(WORKLOAD,))[WORKLOAD]
    print(f"[data] {WORKLOAD}@{scale:g}: |V|={g.num_nodes} |E|={g.num_edges}", flush=True)
    with phase("mapping"):
        perm, part, h_opt, h_id = DeviceMapper((2, 2)).device_permutation(
            g.src, g.dst, g.num_nodes
        )
    print(f"[mapping] engines {perm.tolist()}; byte-weighted ICI hops {h_id:.4f} -> {h_opt:.4f}")
    mesh = make_engines_mesh(site_permutation=perm, devices=devices)
    with phase("distributed bfs"):
        bfs = DistributedEngine(bfs_program(), mesh)
        bfs_out, bfs_it = bfs.run(bfs.load(g, part), source=0)
    with phase("distributed pagerank"):
        gp = prepare_graph("pagerank", g)
        pagerank = DistributedEngine(pagerank_program(), mesh)
        pr_out, pr_it = pagerank.run(pagerank.load(gp, part))
    with phase("references"):
        bfs_ref = reference_bfs(g, 0)
        pr_ref = reference_pagerank(gp)
    print(f"[distributed] bfs {bfs_it} iterations, pagerank {pr_it} iterations")
    require(
        np.array_equal(np.asarray(bfs_out, np.float64), bfs_ref),
        "distributed bfs equals reference_bfs exactly",
    )
    pr_err = np.abs(np.asarray(pr_out, np.float64) - pr_ref)
    print(f"[parity] distributed pagerank max |err| {pr_err.max():.3e}")
    require(pr_err.max() <= PAGERANK_ATOL, f"distributed pagerank within atol {PAGERANK_ATOL:g}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only DistributedEngine on four chips and its references",
    )
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); nothing run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devices)}", file=sys.stderr)
        return 1
    print(f"[device] platform={dev.platform} kind={dev.device_kind} count={len(devices)}", flush=True)
    phase = Phases(f"{dev.device_kind} x{4 if args.four_chips else 1}")
    with phase("total"):
        if args.four_chips:
            run_four_chips(SCALE, phase, devices[:4])
        else:
            run_one_chip(SCALE, phase)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
