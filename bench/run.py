#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload lavaMD.solo --seed 7 --seconds 10 --trace 0

A cell is an entry of ``workloads`` in BENCHMARK.json. With ``--trace 0``
the line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace; both check the simulated
statistics against the plain reference (bench/lib/reference.py) and print
each number compared beside its limit. Without enough accelerator chips
the run exits nonzero and prints no result. See bench/README.md.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    # the persistent compile cache lives at one fixed path inside the
    # checkout; the program takes the directory this variable names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [str(BENCH / "lib"), str(BENCH.parent / "src")]
    import harness
    return harness.run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
