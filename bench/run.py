#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload pokec.front --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic mix, limits and metrics are found by
name from `BENCHMARK.json` (see `bench/common.py`).  The graph is made from
`--seed`; set-up warms every shape the window uses; the window measures
whole units for `--seconds`; the check then compares what the window
produced with the benchmark's own references.  Every line on standard error
names the device, and its last lines are each number compared beside its
limit.  The last line of standard output is the result object.  Without a
TPU, or with fewer chips than the cell asks for, it exits 3 and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv: list[str] | None = None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import common
    from bench.harness import run_cell

    bm = common.load_benchmark(root)
    cell = common.cell(bm, args.workload)
    common.enable_compile_cache(root)
    try:
        devices = common.tpu_devices(cell["chips"])
    except common.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 3
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), devices,
                      bm=bm, t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
