#!/usr/bin/env python3
"""The control: what each number the check compares reads when the work is
done one precision below what the configuration states (float32), which is
what every limit has to catch; and, with `--plant`, what the numbers read
with a fault of `bench/faults.py` planted, or in sound runs.  The
benchmark's own runs never run it.

    python3 bench/control.py --workload pokec.front --seeds 11,12,13
    python3 bench/control.py --workload pokec.front --seeds 11,12,13 --plant skip_descent
    python3 bench/control.py --workload pokec.front --seeds 1,2,3 --plant none

Per seed, in one process, through the same harness as a benchmark run (one
unit in the window):

- `--plant lower_precision` (the default): the program with its own
  lower-precision path switched on, every float32 contraction of the jax
  path at default precision (one bfloat16 pass on a TPU;
  `repro/precision.py` states "highest"); and the reference itself in
  bfloat16 where the program has no such path: PageRank's ranks (for
  `pagerank_rel`) and where it stops (for `pagerank_stop_rel`), and the
  window recursion of the network model under every arm (for `nocsim_rel`
  and `nocsim_credit_rel`), each against the float64 reference on the same
  inputs;
- `--plant <fault>`: the program with that fault planted;
- `--plant none`: the program as it is, for the sound readings.

Prints one JSON line per seed and exits 0 when every seed came out as it
should: not correct with a plant, correct without.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def lower_precision(work) -> None:
    """Switch the program's lower-precision path on, before anything of it
    compiles."""
    import repro.experiments.batched as batched
    import repro.experiments.placement_batch as placement_batch
    import repro.nocsim.credit as credit

    for module in (batched, placement_batch, credit):
        module.DOT_PRECISION = "default"


def bf16_readings(work, units) -> dict:
    """The float numbers of a sweep cell as the bfloat16 reference reads them."""
    import math

    import ml_dtypes
    import numpy as np

    from bench import reference as ref

    bf16 = ml_dtypes.bfloat16
    g = work.graph
    out = {}
    if "pagerank" in work.grid.algorithms:
        cap = ref.SWEEP_PAGERANK_ITERATIONS
        want = ref.pagerank(g.src, g.dst, g.num_nodes)
        got = ref.pagerank(g.src, g.dst, g.num_nodes, iterations=want[1], dtype=bf16)
        out["pagerank_rel"] = float(
            (np.abs(got[0].astype(np.float64) - want[0]) / want[0]).max())
        stopped = ref.pagerank(g.src, g.dst, g.num_nodes, dtype=bf16)[1]
        deltas = ref.pagerank(g.src, g.dst, g.num_nodes, iterations=cap)[4]
        out["pagerank_stop_rel"] = ref.stop_violation(deltas, stopped, cap)
        out["pagerank_bf16_iterations"] = stopped
        out["pagerank_iterations"] = want[1]
    traces = {a: ref.bfs(g.src, g.dst, g.num_nodes, 0) if a == "bfs" else ref.pagerank(g.src, g.dst, g.num_nodes)
              for a in work.grid.algorithms}
    out["nocsim_rel"] = out["nocsim_credit_rel"] = 0.0
    unit = units[0]
    for i, c in enumerate(unit["configs"]):
        _, iters, eact, vact = traces[c.algorithm][:4]
        vpart, epart = ref.partition(c.partitioner, g.src, g.num_nodes, c.num_parts)
        m = ref.traffic(vpart, epart, g.src, eact, vact, c.num_parts)
        topo = ref.Topology(c.topology, c.num_parts)
        sites = unit["sites"][i]
        for routing, depth in work.expected_arms():
            kw = dict(routing=routing, depth=depth)
            want = ref.contended(m, sites, topo, iters, c.num_parts, **kw)
            got = ref.contended(m, sites, topo, iters, c.num_parts, dtype=bf16, **kw)
            name = "nocsim_credit_rel" if depth is not None and math.isfinite(depth) else "nocsim_rel"
            for k in want:
                out[name] = max(out[name], abs(got[k] - want[k]) / abs(want[k]))
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds, three or more")
    ap.add_argument("--seconds", type=float, default=1.0, help="window: one unit is enough")
    ap.add_argument("--plant", default="lower_precision",
                    help="lower_precision, a fault of bench/faults.py, or none")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import common, faults
    from bench.harness import run_cell

    if args.plant not in ("lower_precision", "none") and args.plant not in faults.FAULTS:
        ap.error(f"--plant {args.plant!r} is none of lower_precision, none, {', '.join(faults.FAULTS)}")

    bm = common.load_benchmark(root)
    cell = common.cell(bm, args.workload)
    common.enable_compile_cache(root)
    try:
        devices = common.tpu_devices(cell["chips"])
    except common.NoChip as e:
        print(f"control: {e}; nothing run", file=sys.stderr)
        return 3
    as_expected = True
    limits = common.limits(args.workload)
    planted = []  # a fault is planted once: each plant wraps what is there
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        kept = {}

        def prepare(work):
            kept["work"] = work
            if args.plant == "lower_precision":
                lower_precision(work)
            elif args.plant != "none" and not planted:
                faults.FAULTS[args.plant](setattr)
                planted.append(args.plant)

        kept_units = {}
        result = run_cell(args.workload, seed, args.seconds, False, devices, bm=bm,
                          prepare=prepare, keep_units=kept_units)
        line = {"seed": seed, "plant": args.plant, "correct": result["correct"],
                "readings": {k: v["value"] for k, v in result["checks"].items()}}
        failed = not result["correct"]
        if args.plant == "lower_precision":
            line["bf16_reference"] = bf16_readings(kept["work"], kept_units["units"])
            failed |= any(v > limits[k] for k, v in line["bf16_reference"].items() if k in limits)
        as_expected &= failed if args.plant != "none" else not failed
        line["seconds"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    raise SystemExit(main())
