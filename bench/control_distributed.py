#!/usr/bin/env python3
"""The control of a distributed cell: what each number the check compares
reads when the work is done one precision below the float32 that the
configuration states, which the cell's limits have to catch.  The
benchmark's own runs never run it.

    python3 bench/control_distributed.py --workload pokec.distributed --seeds 11,12,13

Per seed, in one process, through the same harness as a benchmark run (one
unit in the window):

- the program with its own lower-precision path switched on: the engine's
  exchange in bfloat16 (`DistributedEngine(comm_dtype=bfloat16)`), which is
  what the check has to fail;
- the reference itself in bfloat16: PageRank's ranks at the float64
  reference's iteration count (for `pagerank_rel`) and where it stops (for
  `pagerank_stop_rel`), each against the float64 reference on the same
  graph.  BFS levels are small whole numbers, exact in bfloat16, so only
  PageRank can show the control.

Prints one JSON line per seed and exits 0 when every seed came out not
correct, by the program's readings and by the bfloat16 reference's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def bf16_readings(work) -> dict:
    """PageRank's numbers as the bfloat16 reference reads them."""
    import ml_dtypes
    import numpy as np

    from bench import reference as ref

    g = work.graph
    cap = work.max_iterations["pagerank"]
    want = ref.pagerank(g.src, g.dst, g.num_nodes, max_iterations=cap)
    got = ref.pagerank(g.src, g.dst, g.num_nodes, iterations=want[1], dtype=ml_dtypes.bfloat16)
    stopped = ref.pagerank(g.src, g.dst, g.num_nodes, max_iterations=cap, dtype=ml_dtypes.bfloat16)[1]
    deltas = ref.pagerank(g.src, g.dst, g.num_nodes, iterations=cap)[4]
    return {
        "pagerank_rel": float((np.abs(got[0].astype(np.float64) - want[0]) / want[0]).max()),
        "pagerank_stop_rel": ref.stop_violation(deltas, stopped, cap),
        "pagerank_bf16_iterations": stopped,
        "pagerank_iterations": want[1],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds, three or more")
    ap.add_argument("--seconds", type=float, default=0.1, help="window: one unit is enough")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import common
    from bench.harness import run_cell

    bm = common.load_benchmark(root)
    cell = common.cell(bm, args.workload)
    if common.mix(cell)["kind"] != "distributed":
        ap.error(f"{args.workload} is not a distributed cell")
    common.enable_compile_cache(root)
    try:
        devices = common.tpu_devices(cell["chips"])
    except common.NoChip as e:
        print(f"control: {e}; nothing run", file=sys.stderr)
        return 3
    limits = common.limits(args.workload)
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        kept = {}

        def prepare(work):
            kept["work"] = work
            work.comm_dtype = "bfloat16"

        result = run_cell(args.workload, seed, args.seconds, False, devices, bm=bm, prepare=prepare)
        line = {"seed": seed, "plant": "bfloat16 exchange", "correct": result["correct"],
                "readings": {k: v["value"] for k, v in result["checks"].items()},
                "iterations": kept["work"].info["iterations"],
                "bf16_reference": bf16_readings(kept["work"])}
        reference_fails = any(v > limits[k] for k, v in line["bf16_reference"].items() if k in limits)
        as_expected &= not result["correct"] and reference_fails
        line["seconds"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    raise SystemExit(main())
