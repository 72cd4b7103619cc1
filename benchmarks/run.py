"""Benchmark driver — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV and writes a standardized
``experiments/bench/BENCH_<suite>.json`` artifact per suite (schema:
suite, rows[{name, us_per_call, derived}], git_sha, date) — the files CI
uploads so the perf trajectory is comparable across commits.

  fig1  — single-thread simulation time per workload        (paper Fig. 1)
  fig5  — parallel speed-up vs thread/device count          (paper Fig. 5)
  fig6  — static vs dynamic scheduler                       (paper Fig. 6)
  fig7  — CTAs per kernel                                   (paper Fig. 7)
  det   — determinism across modes/devices/schedulers       (paper §1/§3)
  dse   — batched config sweep vs solo-run loop             (DSE layer)
  grid  — batched workloads × configs grid vs solo loop     (zoo frontend)
  packing — bucketed ragged packing vs monolithic vs solo loop, plus
            compile-cache cold/warm                         (RunPlan, PR 8)
  mesh  — distributed grid sweep vs 2-D ('cfg','sm') mesh shape
  tables — table-valued vs scalar-only dyn pytree lanes/sec (DynConfig)
  traces — real-trace ingest time + trace-row vs zoo-row lanes/sec
  search — analytic surrogate configs/sec vs engine lanes/sec, and
           search() vs exhaustive sweep wall clock       (core/search.py)
  serving — continuously batched sim server: jobs/sec, p50/p99 latency,
            warm vs cold, vs one-process-per-job       (core/service.py)
  roofline — per-(arch×shape×mesh) roofline terms           (§Roofline)
  kernels  — Pallas kernel microbenchmarks
"""
from __future__ import annotations

import argparse
import os
import sys

# runnable as `python benchmarks/run.py` from anywhere: the `benchmarks`
# package lives at the repo root, not under src/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def perf_gate() -> list:
    """Perf-trajectory gate (ROADMAP open item): compare the speedup
    ratios measured THIS run against the committed reference
    (benchmarks/perf_reference.json).  Each reference entry names a suite
    artifact under experiments/bench/ (``file``, default
    ``<key>_sweep.json``) and a ratio key inside it (``metric``, default
    ``speedup``); both sides of every ratio are timed on the same host in
    the same process, so machine speed cancels out.  A gated entry whose
    suite was not run this time is skipped with a note (the full bench
    run exercises them all).  Returns a list of failure strings; empty =
    gate passed."""
    import json

    here = os.path.dirname(os.path.abspath(__file__))
    ref_path = os.path.join(here, "perf_reference.json")
    with open(ref_path) as f:
        ref = json.load(f)
    fails = []
    for key, spec in ref.items():
        if key.startswith("_") or not isinstance(spec, dict):
            continue
        fname = spec.get("file", f"{key}_sweep.json")
        metric = spec.get("metric", "speedup")
        cur_path = os.path.join(here, "..", "experiments", "bench", fname)
        try:
            with open(cur_path) as f:
                cur = json.load(f)
        except FileNotFoundError:
            print(f"[gate] {key}: {fname} not produced this run — skipped "
                  f"(run --only {key} or the full suite to gate it)")
            continue
        tol = float(spec.get("tolerance", 0.25))
        floor = float(spec[metric]) * (1.0 - tol)
        got = float(cur[metric])
        verdict = "OK" if got >= floor else "REGRESSION"
        print(f"[gate] {key} {metric}: {got:.3f}x (reference "
              f"{spec[metric]}x, floor {floor:.3f}x at -{tol:.0%}) "
              f"{verdict}")
        if got < floor:
            fails.append(
                f"{key} {metric} {got:.3f}x < floor {floor:.3f}x — "
                f"regressed vs benchmarks/perf_reference.json; if "
                "intentional, update the reference with the measured "
                "value")
    return fails


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: fig1 fig5 fig6 fig7 det dse grid packing "
                         "mesh tables traces search serving roofline "
                         "kernels")
    ap.add_argument("--fast", action="store_true",
                    help="skip subprocess device sweeps")
    ap.add_argument("--gate", action="store_true",
                    help="fail (exit 1) when this run's batched-grid "
                         "speedup regresses >tolerance vs "
                         "benchmarks/perf_reference.json")
    args = ap.parse_args()
    if args.gate and args.only is not None:
        # the gate needs the gated suites' artifacts
        args.only = list(args.only) + [
            s for s in ("grid", "packing", "search", "serving")
            if s not in args.only]

    from repro.core.plan import enable_persistent_cache
    enable_persistent_cache()

    from benchmarks import (determinism, dse_sweep, fig1_sim_time,
                            fig5_speedup, fig6_scheduler, fig7_ctas,
                            grid_sweep, kernels_bench, mesh_sweep, packing,
                            roofline, search_bench, serving, table_sweep,
                            traces_bench)
    from benchmarks.common import save_bench

    suites = {
        "fig7": fig7_ctas.run,
        "roofline": roofline.run,
        "kernels": kernels_bench.run,
        "fig1": fig1_sim_time.run,
        "fig6": fig6_scheduler.run,
        "fig5": (lambda: fig5_speedup.run(measure_shard=not args.fast)),
        "det": determinism.run,
        "dse": dse_sweep.run,
        "grid": grid_sweep.run,
        "packing": packing.run,
        "mesh": (lambda: mesh_sweep.run(fast=args.fast)),
        "tables": table_sweep.run,
        "traces": traces_bench.run,
        "search": search_bench.run,
        "serving": serving.run,
    }
    # a suite that raises ends the run with its traceback and a nonzero
    # exit: no artifact is written for it, and no later suite runs
    rows = []
    for name, fn in suites.items():
        if args.only and name not in args.only:
            continue
        suite_rows = fn()
        save_bench(name, suite_rows)
        rows.extend(suite_rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.gate:
        fails = perf_gate()
        for msg in fails:
            print(f"[gate] FAIL: {msg}")
        if fails:
            sys.exit(1)


if __name__ == "__main__":
    main()
