"""Simulator launcher — run paper benchmarks or LM-derived workloads.

  python -m repro.launch.simulate --workload lavaMD --mode vmap
  python -m repro.launch.simulate --arch qwen2-72b --shape train_4k
  python -m repro.launch.simulate --workload hotspot --scale 1.0 --out r.json

Prints the comparable stats, then the simulated cycles and
warp-instructions with compile seconds apart from execute seconds.
"""
from __future__ import annotations

import argparse
import json

from repro.configs import SHAPES, get_config
from repro.core import stats as S
from repro.core.engine import build_simulation
from repro.core.parallel import make_sm_runner
from repro.core.plan import RunPlan, enable_persistent_cache
from repro.core.sweep import timed_call
from repro.sim.config import RTX3080TI
from repro.sim.state import init_state
from repro.workloads import arch_workload, make_workload


def run_simulation(workload, cfg, mode: str = "vmap",
                   max_cycles: int = 1 << 17) -> tuple:
    """One solo run of ``workload`` on ``cfg``: build the whole-workload
    program, lower + compile it, then execute it, timed apart
    (core/sweep.py:timed_call).  Returns (finalized stats, timings)."""
    run, scfg, dyn = build_simulation(workload, cfg,
                                      make_sm_runner(cfg, mode),
                                      RunPlan(max_cycles=max_cycles))
    st, timings = timed_call(run, init_state(scfg), dyn)
    return S.finalize(st), timings


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="")
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--scale", type=float, default=0.03)
    ap.add_argument("--mode", choices=["seq", "vmap"], default="vmap")
    ap.add_argument("--max-cycles", type=int, default=1 << 17)
    ap.add_argument("--out", default="", metavar="FILE",
                    help="also write the run (workload, scale, mode, "
                         "max_cycles, timeouts, comparable stats) as JSON")
    args = ap.parse_args(argv)
    enable_persistent_cache()

    cfg = RTX3080TI
    if args.arch:
        w = arch_workload(get_config(args.arch), SHAPES[args.shape])
    else:
        w = make_workload(args.workload or "hotspot", scale=args.scale)
    out, tm = run_simulation(w, cfg, args.mode, args.max_cycles)
    comparable = S.comparable(out)
    print(json.dumps(comparable, indent=1))
    print(f"[simulate] {w.name}: {out['cycles']} GPU cycles, "
          f"{out['issued']} warp-instructions, ipc={out['ipc']}, "
          f"timeouts={out['timeouts']}, compile_s={tm['compile_s']}, "
          f"execute_s={tm['execute_s']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": w.name, "scale": args.scale,
                       "mode": args.mode, "max_cycles": args.max_cycles,
                       "config": "RTX3080TI", "timeouts": out["timeouts"],
                       "stats": comparable}, f, indent=1, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
