"""Design-space-exploration launcher — N GPU configs, ONE compiled program.

  python -m repro.launch.dse --n 8 --workload hotspot --scale 0.02
  python -m repro.launch.dse --base 3080ti --axis dram_row_penalty \\
      --values 8,16,24,48
  python -m repro.launch.dse --n 8 --sample-lat fp32 2 8 --check
  python -m repro.launch.dse --n 8 --check     # verify vs solo runs
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python -m repro.launch.dse --n 8 --mesh 2 2 --check

``--mesh A B`` shards the config lanes over a 2-D ('cfg', 'sm') device
mesh (core/distribute.py) — A cfg-devices × B sm-devices, A×B devices
total (on CPU, force them with XLA_FLAGS before jax initializes).

``--sample-lat CLASS LO HI`` (repeatable; likewise ``--sample-disp``)
sweeps a PER-CLASS entry of the typed DynConfig's timing tables: the N
lanes step the result latency (or dispatch interval) of instruction class
CLASS (fp32/int32/sfu/tensor/ldg/stg/bar) evenly from LO to HI — the
table leaves are traced, so the whole per-class sweep is still one
compiled program.  The ldg latency entry is inert (load latency is
cache-dependent: see sim/config.py:CoreDyn).

Without --axis/--sample-*, a default grid is swept: L2 latency × scheduler
(GTO/LRR), the two knobs with the clearest IPC signal on the paper's
benchmarks.  All lanes share one StaticConfig shape — only traced timing
parameters and the scheduler selector differ, which is what makes the
whole sweep a single ``jit(vmap(engine))`` call (core/sweep.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.core import stats as S
from repro.core import telemetry as T
from repro.core.engine import run_workload
from repro.core.parallel import make_sm_runner
from repro.core.plan import enable_persistent_cache
from repro.core.sweep import sweep
from repro.launch.cli import (add_plan_args, add_sample_args,
                              add_search_args, plan_from_args, profile_ctx)
from repro.sim.config import (DYNAMIC_FIELDS, RTX3080TI, TINY, GPUConfig,
                              class_index, split_config)
from repro.sim.state import init_state
from repro.workloads import make_workload

BASES = {"tiny": TINY, "3080ti": RTX3080TI}


def default_grid(base: GPUConfig, n: int) -> list:
    """n configs: alternate GTO/LRR while stepping L2 latency."""
    out = []
    for i in range(n):
        out.append(dataclasses.replace(
            base,
            l2_lat=base.l2_lat // 2 + (i // 2) * base.l2_lat // 2,
            scheduler="gto" if i % 2 == 0 else "lrr"))
    return out


def axis_grid(base: GPUConfig, axis: str, values: list) -> list:
    if axis == "scheduler":
        return [dataclasses.replace(base, scheduler=v) for v in values]
    if axis not in DYNAMIC_FIELDS:
        raise SystemExit(f"--axis must be one of {DYNAMIC_FIELDS} or "
                         f"'scheduler', got {axis!r}")
    return [dataclasses.replace(base, **{axis: int(v)}) for v in values]


def sample_table_grid(base: GPUConfig, n: int, sample_lat=(),
                      sample_disp=(), seed: int = None) -> list:
    """n configs sampling per-class table entries over [lo, hi].

    ``sample_lat`` / ``sample_disp``: sequences of (class_name, lo, hi)
    triples; several triples vary jointly across the same n lanes.
    Default: lane i gets entry = round(lo + i/(n-1) * (hi-lo)) —
    deterministic linear steps, endpoints included.  With ``seed`` each
    lane instead draws every sampled entry uniformly from [lo, hi]
    (PCG64: same seed, same lanes — the randomized-probe complement to
    the linear sweep, shared by both launchers via --sample-seed)."""
    rng = (np.random.Generator(np.random.PCG64(seed))
           if seed is not None else None)
    out = []
    for i in range(n):
        frac = i / max(n - 1, 1)
        lat = list(base.lat_of_class)
        disp = list(base.disp_of_class)
        for table, samples in ((lat, sample_lat), (disp, sample_disp)):
            for cls, lo, hi in samples:
                lo, hi = int(lo), int(hi)
                table[class_index(str(cls))] = (
                    int(rng.integers(lo, hi + 1)) if rng is not None
                    else round(lo + frac * (hi - lo)))
        out.append(dataclasses.replace(base, lat_of_class=tuple(lat),
                                       disp_of_class=tuple(disp)))
    return out


def describe(cfg: GPUConfig) -> dict:
    d = {k: getattr(cfg, k) for k in DYNAMIC_FIELDS}
    d["scheduler"] = cfg.scheduler
    # always present so every row of a sweep has the same keys (a sampled
    # lane can land exactly on the default table)
    d["lat"] = list(cfg.lat_of_class)
    d["disp"] = list(cfg.disp_of_class)
    return d


def _solo_checker(scfg, w, max_cycles):
    """One compiled UNBATCHED program that replays any lane solo: dyn is
    a traced argument, so all the solo runs share a single compilation."""
    packed = [k.pack() for k in w.kernels]
    runner = make_sm_runner(scfg, "vmap")
    return jax.jit(lambda dyn: run_workload(
        init_state(scfg), packed, scfg, dyn, runner, max_cycles))


def run_search(args, plan, base, w):
    """--search: analytic-prune search instead of a fixed-grid sweep."""
    from repro.core import analytic
    from repro.core.search import SearchSpace, search

    space = SearchSpace.from_base(base, spread=args.search_spread,
                                  sample_lat=args.sample_lat,
                                  sample_disp=args.sample_disp)
    t0 = time.time()
    with profile_ctx(args):
        result = search(w, space, plan=plan,
                        n_candidates=args.search_cands,
                        calibrate_from=None if args.no_manifest else "",
                        log=print)
    wall = time.time() - t0

    rep = result.report()
    print(json.dumps(rep, indent=1))
    print(f"[dse] search {w.name}: scored {result.n_scored} candidates "
          f"analytically, verified {result.n_verified} cycle-accurately "
          f"over {len(result.rounds)} rounds, best={result.best_cycles} "
          f"cycles, wall={wall:.1f}s")

    if not args.no_manifest:
        # verified lanes + stats + the workload's feature vector: exactly
        # the rows calibration_rows_from_manifests harvests to warm-start
        # the next search of this StaticConfig
        mpath = T.write_manifest(
            "search", scfg=result.scfg, mesh_shape=args.mesh,
            timings={"wall_s": round(wall, 4)},
            stats=[st for _, _, st in result.verified],
            lanes=[analytic.describe_vec(v) for v, _, _ in result.verified],
            extra={"workload": w.name, "plan": plan.describe(),
                   "features": result.features.tolist(),
                   "search": rep, "profile_dir": args.profile or None})
        print(f"[dse] manifest: {mpath}")

    if args.check:
        solo_run = _solo_checker(result.scfg, w, args.max_cycles)
        for i, (vec, _, st) in enumerate(result.verified):
            dyn = split_config(result.scfg, analytic.decode(vec))[1]
            solo = S.comparable(S.finalize(solo_run(dyn)))
            lane = S.comparable(st)
            assert lane == solo, (i, lane, solo)
        print(f"[dse] check OK: all {result.n_verified} verified lanes "
              "bit-exact vs solo")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--base", choices=sorted(BASES), default="tiny")
    ap.add_argument("--workload", default="hotspot")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--axis", default="",
                    help="sweep one config field instead of the default grid")
    ap.add_argument("--values", default="",
                    help="comma-separated values for --axis")
    ap.add_argument("--check", action="store_true",
                    help="verify every lane against a solo engine run")
    add_sample_args(ap, when="the N lanes")
    add_search_args(ap)
    add_plan_args(ap)
    args = ap.parse_args(argv)
    enable_persistent_cache(args.cache_dir or None)
    plan = plan_from_args(args)

    base = BASES[args.base]
    if args.search:
        if args.axis:
            raise SystemExit("--search and --axis are separate modes; "
                             "pick one (--sample-* triples shape the "
                             "search box instead)")
        w = make_workload(args.workload, scale=args.scale)
        return run_search(args, plan, base, w)
    if args.axis and (args.sample_lat or args.sample_disp):
        raise SystemExit("--axis and --sample-lat/--sample-disp are "
                         "separate sweep modes; pick one")
    if args.axis:
        values = [v for v in args.values.split(",") if v]
        if not values:
            raise SystemExit("--axis needs --values v1,v2,...")
        cfgs = axis_grid(base, args.axis, values)
    elif args.sample_lat or args.sample_disp:
        cfgs = sample_table_grid(base, args.n, args.sample_lat,
                                 args.sample_disp, seed=args.sample_seed)
    else:
        cfgs = default_grid(base, args.n)

    w = make_workload(args.workload, scale=args.scale)
    t0 = time.time()
    with profile_ctx(args):
        result = sweep(w, cfgs, plan=plan)
    wall = time.time() - t0

    rows = []
    for cfg, st in zip(cfgs, result.stats):
        rows.append(dict(describe(cfg), cycles=st["cycles"], ipc=st["ipc"],
                         l1_miss=st["l1_miss"], l2_miss=st["l2_miss"],
                         dram_req=st["dram_req"]))
    print(json.dumps(rows, indent=1))
    where = (f"{args.mesh[0]}x{args.mesh[1]} ('cfg','sm') mesh"
             if args.mesh else "one device")
    tm = result.timings
    print(f"[dse] {len(cfgs)} configs × {w.name}: one compiled call on "
          f"{where}, wall={wall:.1f}s "
          f"(compile={tm.get('compile_s')}s execute={tm.get('execute_s')}s "
          f"{tm.get('lanes_per_s')} lanes/s)")

    if not args.no_manifest:
        tls = result.timelines()
        mpath = T.write_manifest(
            "dse", scfg=result.scfg, mesh_shape=args.mesh,
            timings=dict(tm, wall_s=round(wall, 4)),
            stats=result.stats,
            timelines={k: v.tolist() for k, v in tls.items()} or None,
            lanes=[describe(c) for c in cfgs],
            extra={"workload": w.name, "plan": plan.describe(),
                   "profile_dir": args.profile or None})
        print(f"[dse] manifest: {mpath}")

    if args.check:
        solo_run = _solo_checker(result.scfg, w, args.max_cycles)
        for i, cfg in enumerate(cfgs):
            solo = S.comparable(S.finalize(solo_run(split_config(cfg)[1])))
            lane = S.comparable(result.stats[i])
            assert lane == solo, (i, lane, solo)
        print(f"[dse] check OK: all {len(cfgs)} lanes bit-exact vs solo")


if __name__ == "__main__":
    main()
