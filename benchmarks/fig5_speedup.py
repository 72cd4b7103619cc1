"""Fig. 5 analogue — parallel speed-up vs. "thread" count.

Three complementary measurements (this container has ONE physical core, so
wall-clock multi-device scaling is not physically observable — DESIGN.md §7):

  a. measured: sequential (lax.map over SMs) vs vectorized (vmap) wall time
     — the single-chip SIMD speed-up of the parallel region;
  b. measured: sharded-mode wall time at 1/2/4/8/16 host devices
     (subprocess per count; flat on one core, reported honestly; a CPU
     rehearsal only — refused on an accelerator, common.cpu_child_env);
  c. modeled: Amdahl speed-up from the *measured deterministic work
     distribution* — parallel work = per-SM active-warp-cycles, serial work
     = memory-system events — reproducing the paper's curve shapes
     (lavaMD near-linear, myocyte flat, strong correlation with Fig. 1).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import (DEFAULT_BENCHES, MAX_CYCLES, SIM_SCALE,
                               run_shard_worker, save_json)
from repro.core import stats as S
from repro.core.engine import simulate
from repro.core.parallel import make_sm_runner, sm_permutation
from repro.sim.config import RTX3080TI
from repro.workloads import make_workload

THREADS = (2, 4, 8, 16)


def modeled_speedup(per_sm_work: np.ndarray, serial_work: float,
                    n_dev: int, policy: str, cfg) -> float:
    perm = sm_permutation(cfg, n_dev, policy)
    w = per_sm_work[perm].reshape(n_dev, -1).sum(axis=1)
    total = per_sm_work.sum() + serial_work
    par = w.max() + serial_work
    return float(total / max(par, 1))


SHARD_BENCHES = ("lavaMD", "myocyte", "cut_1", "sssp")


def run(benches=None, shard_devices=(2, 8, 16),
        measure_shard: bool = True) -> list[dict]:
    cfg = RTX3080TI
    rows = []
    for name in benches or DEFAULT_BENCHES:
        w = make_workload(name, scale=SIM_SCALE)

        def wall(mode):
            runner = make_sm_runner(cfg, mode)
            t0 = time.perf_counter()
            st = simulate(w, cfg, runner, max_cycles=MAX_CYCLES)
            jax.block_until_ready(st["ctrl"]["total_cycles"])
            return time.perf_counter() - t0, st

        t_seq, st = wall("seq")
        t_vmap, st2 = wall("vmap")
        out = S.finalize(st)
        assert S.comparable(out) == S.comparable(S.finalize(st2))
        per_sm = out["warp_cycles_per_sm"].astype(np.float64)
        serial = float(out["l2_hit"] + out["l2_miss"] + out["dram_req"])
        model = {d: round(modeled_speedup(per_sm, serial, d, "static", cfg),
                          2) for d in THREADS}
        rows.append({
            "name": f"fig5/{name}/vectorize",
            "us_per_call": t_vmap * 1e6,
            "derived": f"seq_s={t_seq:.2f};speedup={t_seq / t_vmap:.2f}",
        })
        rows.append({
            "name": f"fig5/{name}/modeled",
            "us_per_call": 0.0,
            "derived": ";".join(f"x{d}={v}" for d, v in model.items()),
        })
        if measure_shard and name in SHARD_BENCHES:
            walls = {d: round(run_shard_worker(name, d)["wall_s"], 3)
                     for d in shard_devices}
            rows.append({
                "name": f"fig5/{name}/sharded_wall",
                "us_per_call": 0.0,
                "derived": ";".join(f"d{d}={v}" for d, v in walls.items()),
            })
    save_json("fig5_speedup", {"rows": rows})
    return rows
