#!/usr/bin/env python3
"""Readings that set the limits of the output check, for one cell.

    python3 bench/control.py --workload lavaMD.solo --seeds 11 12 13

For each seed it makes one call of the cell's program at the cell's own
size, picks the lanes a run would compare, and prints two readings of
every number the check compares: the program's gaps to the plain
reference (the lower readings), and the control's, where the control is
the reference itself put in the program's place and run with a coarser
quantum, Δ = 2 × icnt_lat. That breaks the exactness window the
configuration states (Δ ≤ icnt_lat), the shortcut that would tempt a
faster simulator. A limit lies between the largest lower reading and the
smallest upper one. The benchmark's own runs do not run this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [str(BENCH / "lib"), str(BENCH.parent / "src")]
    import jax
    import numpy as np

    import cells
    import check
    import harness
    import program

    cell = cells.cell(args.workload)
    cfg = cells.config(cell["config"])
    traffic = cells.traffic(cell["traffic"])
    kind = cells.kind(traffic["kind"])
    control_q = 2 * int(cfg["gpu"]["icnt_lat"])
    memo = {}

    def stats_of(point, quantum=None):
        key = (json.dumps(point, sort_keys=True), quantum)
        if key not in memo:
            memo[key] = check.reference_stats(cfg, [point], program.MAX_CYCLES,
                                              quantum)[0]
        return memo[key]

    lower, upper = {}, {}
    for seed in args.seeds:
        t = time.perf_counter()
        runner = kind.build(cfg, traffic, seed, jax.devices())
        runner.compile()
        calls = [program.lane_stats(harness.complete(runner, runner.init()),
                                    len(runner.points), runner.batched)]
        lanes = runner.sample(np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([seed, 1]))), calls[0])
        points = [runner.points[i] for i in lanes]
        ref = [stats_of(p) for p in points]
        ctl = [stats_of(p, control_q) for p in points]
        got = check.gaps(cfg, calls, lanes, ref)
        bad = {k: max(abs(c[k] - r[k]) for c, r in zip(ctl, ref))
               for k in check.STATS}
        for k in check.KEYS:
            lower[k] = max(lower.get(k, 0), got[k])
        for k in check.STATS:
            upper[k] = min(upper.get(k, bad[k]), bad[k])
        print(json.dumps({"seed": seed, "lanes": lanes, "program": got,
                          "control": bad,
                          "seconds": time.perf_counter() - t}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "limits": check.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
