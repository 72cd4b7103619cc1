"""Workload-zoo launcher — list the zoo, run one workload, or sweep a
whole benchmarks × configs grid as ONE compiled program.

  python -m repro.launch.zoo --list
  python -m repro.launch.zoo --run random_gather --scale 0.05
  python -m repro.launch.zoo --grid 4 4 --check     # W×C lanes vs solo
  python -m repro.launch.zoo --trace tests/data/traces --check
  python -m repro.launch.zoo --trace tests/data/traces --grid 3 4 --check
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python -m repro.launch.zoo --grid 4 4 --mesh 2 2 --check

``--trace FILE|DIR`` ingests real Accel-sim SASS trace subset files
(sim/traceio.py) and registers them in the zoo as ``trace:<stem>``
workloads.  With ``--grid W C`` the trace workloads fill the grid's
workload rows first (synthetic zoo names top up if W exceeds the trace
count) and ride the batched frontend unchanged; trace rows keep their
real CTA counts (``--scale`` applies to synthetic generators only).
Without ``--grid``/``--run`` an ingest summary is printed per trace, and
``--check`` additionally runs an (all traces × 2 configs) grid verifying
every lane bit-exact vs its solo run — the CI trace smoke.

``--grid W C`` takes the first W zoo workloads (registry order) and a
C-point config grid (launch/dse.py:default_grid — L2 latency × scheduler)
and runs the full grid in one ``jit(vmap(vmap(...)))`` call
(core/sweep.py:grid_sweep).  ``--check`` reruns every (workload, config)
pair solo and asserts the grid lane is bit-identical — including lanes
whose workload was padded with NOP slots / empty kernels (core/batch.py).

``--sample-lat CLASS LO HI`` / ``--sample-disp CLASS LO HI`` (repeatable)
replace the default config grid with a per-class timing-table sweep
(launch/dse.py:sample_table_grid): the C lanes step the result latency /
dispatch interval of instruction class CLASS evenly from LO to HI — the
typed DynConfig's table leaves are traced, so benchmarks × per-class
timing points still compile to one program.

``--mesh A B`` distributes the grid over a 2-D ('cfg', 'sm') device mesh
(core/distribute.py): config lanes sharded over A cfg-devices, each
lane's SM axis over B sm-devices.  Needs A×B devices — on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=<A*B>`` before jax
initializes.  ``--check`` still compares against single-device solo runs,
so it proves the distributed lanes bit-exact end to end.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core import stats as S
from repro.core import telemetry as T
from repro.core.engine import simulate
from repro.core.parallel import make_sm_runner
from repro.core.plan import RunPlan, enable_persistent_cache
from repro.core.sweep import grid_sweep
from repro.launch.cli import (add_plan_args, add_sample_args, plan_from_args,
                              profile_ctx)
from repro.launch.dse import (BASES, default_grid, describe,
                              sample_table_grid)
from repro.sim.workloads import (TRACE_INGESTS, register_traces, zoo_names,
                                 zoo_workload)


def run_trace_summary(args, trace_names) -> None:
    """Ingest-summary mode (``--trace`` without --grid/--run): report
    fit stats per trace; with --check, verify an (all traces × 2 cfgs)
    grid bit-exact against solo runs."""
    for name in trace_names:
        ing = TRACE_INGESTS[name]
        s = ing.summary()
        print(f"[zoo] ingested {name}: {s['n_kernels']} kernel(s), "
              f"{s['total_ctas']} CTAs, n_instr={s['n_instr']}, "
              f"fit_err mean={s['fit_err_mean']} max={s['fit_err_max']} "
              f"blocks")
    if args.check:
        workloads = [zoo_workload(n) for n in trace_names]
        cfgs = default_grid(BASES[args.base], 2)
        grid = grid_sweep(workloads, cfgs, plan=plan_from_args(args))
        check_grid_vs_solo(grid, workloads, cfgs, args.max_cycles)
        print(f"[zoo] check OK: {len(workloads)}x{len(cfgs)} trace grid "
              "bit-exact vs solo runs")


def lane_signature(stats: dict) -> dict:
    """What --check compares: the cross-mode-comparable stats plus the
    truncation counter (a grid lane must also time out exactly when its
    solo run does)."""
    return dict(S.comparable(stats), timeouts=stats["timeouts"])


def check_grid_vs_solo(grid, workloads, cfgs, max_cycles: int) -> int:
    """Re-run every (workload, config) pair solo and assert its grid
    lane is bit-identical.  The ONE --check oracle for both grid modes.
    Returns the verified lane count."""
    runner = make_sm_runner(grid.scfg, "vmap")
    solo_plan = RunPlan(max_cycles=max_cycles)   # the padded solo oracle
    for w, workload in enumerate(workloads):
        for c, cfg in enumerate(cfgs):
            solo = lane_signature(S.finalize(simulate(
                workload, cfg, runner, plan=solo_plan)))
            lane = lane_signature(grid.stats[w][c])
            assert lane == solo, (grid.names[w], c, lane, solo)
    return len(workloads) * len(cfgs)


def _scale_for(name: str, scale: float) -> float:
    """Trace-derived workloads keep their real CTA counts; --scale
    applies to the synthetic generators only."""
    return 1.0 if name.startswith("trace:") else scale


def run_grid(args, trace_names=()) -> None:
    n_w, n_c = args.grid
    names = list(trace_names) + [n for n in zoo_names()
                                 if n not in trace_names]
    if n_w > len(names):
        raise SystemExit(f"--grid {n_w} exceeds zoo size {len(names)}")
    base = BASES[args.base]
    workloads = [zoo_workload(n, scale=_scale_for(n, args.scale))
                 for n in names[:n_w]]
    if args.sample_lat or args.sample_disp:
        cfgs = sample_table_grid(base, n_c, args.sample_lat,
                                 args.sample_disp, seed=args.sample_seed)
    else:
        cfgs = default_grid(base, n_c)
    plan = plan_from_args(args)

    t0 = time.time()
    with profile_ctx(args):
        grid = grid_sweep(workloads, cfgs, plan=plan)
    wall = time.time() - t0
    print(json.dumps(grid.table(), indent=1))
    lanes = n_w * n_c
    where = (f"{args.mesh[0]}x{args.mesh[1]} ('cfg','sm') mesh"
             if args.mesh else "one device")
    tm = grid.timings
    print(f"[zoo] grid {n_w} workloads × {n_c} configs = {lanes} lanes "
          f"(bucket_by={plan.bucket_by} layout={plan.layout} "
          f"buckets={tm.get('n_buckets')}) on {where}, wall={wall:.1f}s "
          f"(compile={tm.get('compile_s')}s execute={tm.get('execute_s')}s "
          f"{tm.get('lanes_per_s')} lanes/s)")

    if not args.no_manifest:
        tls = grid.timelines()
        mpath = T.write_manifest(
            "zoo_grid", scfg=grid.scfg, mesh_shape=args.mesh,
            timings=dict(tm, wall_s=round(wall, 4)),
            stats=[dict(grid.stats[w][c], workload=grid.names[w], cfg=c)
                   for w in range(n_w) for c in range(n_c)],
            timelines={k: v.tolist() for k, v in tls.items()} or None,
            lanes=[dict(describe(cfg), workload=grid.names[w], cfg=c)
                   for w in range(n_w) for c, cfg in enumerate(cfgs)],
            extra={"workloads": grid.names, "plan": plan.describe(),
                   "profile_dir": args.profile or None})
        print(f"[zoo] manifest: {mpath}")

    if args.check:
        n = check_grid_vs_solo(grid, workloads, cfgs, args.max_cycles)
        print(f"[zoo] check OK: all {n} lanes bit-exact vs solo runs")


def run_one(args) -> None:
    w = zoo_workload(args.run, scale=_scale_for(args.run, args.scale))
    plan = plan_from_args(args)
    [cfg] = plan.apply_telemetry([BASES[args.base]])
    t0 = time.time()
    with profile_ctx(args):
        st = simulate(w, cfg, make_sm_runner(cfg, "vmap"), plan=plan)
    wall = time.time() - t0
    out = S.finalize(st)
    print(json.dumps(dict(S.comparable(out), ipc=out["ipc"],
                          timeouts=out["timeouts"]), indent=1))
    flag = " [TIMEOUT: truncated at max_cycles]" if out["timeout"] else ""
    print(f"[zoo] {w.name}: {out['cycles']} GPU cycles, ipc={out['ipc']}, "
          f"wall={wall:.1f}s{flag}")

    if not args.no_manifest:
        from repro.sim.config import split_config
        scfg, _ = split_config(cfg)
        tls = ({w.name: T.timeline(st).tolist()}
               if T.enabled(scfg) else None)
        mpath = T.write_manifest(
            "zoo_run", scfg=scfg,
            timings={"wall_s": round(wall, 4), "n_lanes": 1},
            stats=[dict(out, workload=w.name)], timelines=tls,
            lanes=[dict(describe(cfg), workload=w.name)],
            extra={"workloads": [w.name],
                   "profile_dir": args.profile or None})
        print(f"[zoo] manifest: {mpath}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--list", action="store_true",
                    help="list zoo workload names")
    ap.add_argument("--run", default="", help="simulate one zoo workload")
    ap.add_argument("--grid", nargs=2, type=int, metavar=("W", "C"),
                    help="sweep first W workloads × C configs, one program")
    ap.add_argument("--trace", default="", metavar="FILE|DIR",
                    help="ingest Accel-sim SASS trace subset file(s) and "
                         "register them as trace:<stem> zoo workloads")
    ap.add_argument("--base", choices=sorted(BASES), default="tiny")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--check", action="store_true",
                    help="with --grid: verify every lane vs a solo run")
    add_sample_args(ap, when="--grid")
    add_plan_args(ap)
    args = ap.parse_args(argv)
    enable_persistent_cache(args.cache_dir or None)

    if (args.sample_lat or args.sample_disp) and not args.grid:
        raise SystemExit("--sample-lat/--sample-disp shape the config grid "
                         "and need --grid W C")
    trace_names = []
    if args.trace:
        trace_names = register_traces(args.trace)
    if args.list:
        for n in zoo_names():
            print(n)
    elif args.grid:
        run_grid(args, trace_names)
    elif args.run:
        run_one(args)
    elif trace_names:
        run_trace_summary(args, trace_names)
    else:
        raise SystemExit("pick one of --list / --run NAME / --grid W C / "
                         "--trace FILE|DIR")


if __name__ == "__main__":
    main()
