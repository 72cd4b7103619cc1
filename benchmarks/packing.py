"""Packing throughput: did the batching bet pay off?

Three ways to run the same (workload × config) grid, all timed in one
process so machine speed cancels:

  · ``loop``      — W jitted solo programs (dyn traced, so each workload
    compiles once for all its configs), W×C sequential dispatches;
  · ``monolithic``— the pre-PR-8 batched grid: every workload padded to
    the GLOBAL max shape, one program, every lane riding the longest
    lane's while_loop (the 0.62× loser the reference file used to pin);
  · ``bucketed``  — shape-bucketed ragged packing with early exit
    (core/batch.py:bucket_workloads + concat_workloads): one program per
    bucket, each padded only to ITS max, entry-converged padding kernels
    charging zero quanta.

The headline number — ``speedup`` in experiments/bench/packing.json, what
``run.py --gate`` pins — is bucketed-vs-loop: ≥1.0 means one-program
batching beats a loop of solo programs on the heterogeneous zoo grid, on
a single CPU device, which is the bet the ROADMAP recorded.

A second pair of rows prices the compile cache: the bucketed grid's
cold lower+compile wall vs a warm re-run through the in-process AOT
executable cache (core/sweep.py:timed_call) — warm must be ~pure
execution (compile_s == 0).
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import (MAX_CYCLES, SIM_SCALE, grid_workload_names,
                               save_json, timeit)
from repro.core.batch import (bucket_workloads, check_workload_fits,
                              concat_workloads, stack_kernels,
                              stack_workloads)
from repro.core.engine import run_workload_stacked
from repro.core.parallel import make_sm_runner
from repro.core.plan import RunPlan
from repro.core.sweep import (aot_cache_key, batched_init, clear_aot_cache,
                              make_grid_runner, stack_dyn, timed_call)
from repro.launch.dse import default_grid
from repro.sim.config import TINY, split_config
from repro.sim.state import init_state
from repro.sim.workloads import resolve_workload

N_WORKLOADS = 4
N_CONFIGS = 4
MAX_BUCKETS = 3


def run() -> list[dict]:
    names = grid_workload_names(N_WORKLOADS)
    workloads = [resolve_workload(
        n, scale=1.0 if n.startswith("trace:") else SIM_SCALE)
        for n in names]
    cfgs = default_grid(TINY, N_CONFIGS)
    scfg, dyn_batch = stack_dyn(cfgs)
    for w in workloads:
        check_workload_fits(scfg, w)
    max_cycles = min(MAX_CYCLES, 1 << 15)
    n_w = len(workloads)
    lanes = n_w * N_CONFIGS
    plan = RunPlan(max_cycles=max_cycles, bucket_by="shape",
                   max_buckets=MAX_BUCKETS, layout="ragged")

    # -- loop: W solo programs, W×C sequential dispatches -------------------
    sm_runner = make_sm_runner(scfg, "vmap")
    solos = []
    for w in workloads:
        wk = stack_kernels([k.pack() for k in w.kernels])
        solos.append(jax.jit(
            lambda dyn, wk=wk: run_workload_stacked(
                init_state(scfg), wk, scfg, dyn, sm_runner, max_cycles)))
    dyns = [split_config(cfg)[1] for cfg in cfgs]

    def loop():
        outs = [solo(d)["ctrl"]["total_cycles"]
                for solo in solos for d in dyns]
        jax.block_until_ready(outs)

    t_loop = timeit(loop, warmup=1, iters=3)

    # -- monolithic: one program, global max padding ------------------------
    # the grid runner DONATES its state batch, so every call builds a fresh
    # one (a broadcast + copy — the same price a real grid_sweep pays)
    runner = make_grid_runner(scfg, max_cycles=max_cycles)
    mono = stack_workloads(workloads)
    t_mono = timeit(
        lambda: jax.block_until_ready(runner(
            batched_init(scfg, n_w, N_CONFIGS), mono, dyn_batch)),
        warmup=1, iters=3)

    # -- bucketed: shape buckets, ragged layout, early exit -----------------
    groups = bucket_workloads(workloads, by=plan.bucket_by,
                              max_buckets=plan.max_buckets)
    stacks = [concat_workloads([workloads[i] for i in g]) for g in groups]

    # compile cache, cold vs warm: a fresh AOT-lower+compile of every
    # bucket program vs a re-run through the executable cache
    clear_aot_cache()
    key = aot_cache_key(scfg, plan, "grid")

    def buckets_timed():
        compile_s, execute_s = 0.0, 0.0
        status = set()
        for g, s in zip(groups, stacks):
            _, tm = timed_call(runner, batched_init(scfg, len(g), N_CONFIGS),
                               s, dyn_batch, n_lanes=lanes, cache_key=key)
            compile_s += tm["compile_s"]
            execute_s += tm["execute_s"]
            status.add(tm.get("aot_cache", "none"))
        return compile_s, execute_s, "+".join(sorted(status))

    t0 = time.perf_counter()
    cold_compile, _, cold_status = buckets_timed()
    t_cold_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm_compile, _, warm_status = buckets_timed()
    t_warm_wall = time.perf_counter() - t0

    # steady-state bucketed execution (programs compiled above)
    def bucketed():
        outs = [runner(batched_init(scfg, len(g), N_CONFIGS), s,
                       dyn_batch)["ctrl"]["total_cycles"]
                for g, s in zip(groups, stacks)]
        jax.block_until_ready(outs)

    t_buck = timeit(bucketed, warmup=1, iters=3)

    # -- donation probe: is the state batch really not copied? --------------
    # donate=True must free the input buffers (the output aliases them →
    # peak live state is 1×); donate=False keeps input AND output live
    # (2×).  Results must be bit-identical either way.
    def live_mb(*trees):
        return sum(x.nbytes for t in trees
                   for x in jax.tree_util.tree_leaves(t)
                   if not x.is_deleted()) / 1e6

    runner_nd = make_grid_runner(scfg, max_cycles=max_cycles, donate=False)
    st_d = batched_init(scfg, n_w, N_CONFIGS)
    state_mb = live_mb(st_d)
    out_d = jax.block_until_ready(runner(st_d, mono, dyn_batch))
    donate_live = live_mb(st_d, out_d)
    st_nd = batched_init(scfg, n_w, N_CONFIGS)
    out_nd = jax.block_until_ready(runner_nd(st_nd, mono, dyn_batch))
    nodonate_live = live_mb(st_nd, out_nd)
    donation_freed = all(x.is_deleted()
                         for x in jax.tree_util.tree_leaves(st_d))
    bit_exact = all(
        (a == b).all() for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(out_d)),
            jax.tree_util.tree_leaves(jax.device_get(out_nd))))
    assert donation_freed, "donated state batch was NOT freed (copied?)"
    assert bit_exact, "donated vs undonated grid results differ"

    speedup_vs_loop = t_loop / t_buck
    rows = [{
        "name": f"packing/loop_{n_w}x{N_CONFIGS}",
        "us_per_call": t_loop * 1e6,
        "derived": f"lanes_per_s={lanes / t_loop:.2f}",
    }, {
        "name": f"packing/monolithic_{n_w}x{N_CONFIGS}",
        "us_per_call": t_mono * 1e6,
        "derived": (f"lanes_per_s={lanes / t_mono:.2f} "
                    f"vs_loop={t_loop / t_mono:.2f}x"),
    }, {
        "name": (f"packing/bucketed_{n_w}x{N_CONFIGS}"
                 f"_b{len(groups)}_ragged"),
        "us_per_call": t_buck * 1e6,
        "derived": (f"lanes_per_s={lanes / t_buck:.2f} "
                    f"vs_loop={speedup_vs_loop:.2f}x "
                    f"vs_monolithic={t_mono / t_buck:.2f}x"),
    }, {
        "name": "packing/compile_cold",
        "us_per_call": t_cold_wall * 1e6,
        "derived": f"compile_s={cold_compile:.2f} aot={cold_status}",
    }, {
        "name": "packing/compile_warm",
        "us_per_call": t_warm_wall * 1e6,
        "derived": f"compile_s={warm_compile:.2f} aot={warm_status}",
    }, {
        "name": f"packing/donation_{n_w}x{N_CONFIGS}",
        "us_per_call": 0.0,
        "derived": (f"state_mb={state_mb:.2f} "
                    f"live_donate_mb={donate_live:.2f} "
                    f"live_nodonate_mb={nodonate_live:.2f} "
                    f"freed={donation_freed} bit_exact={bit_exact}"),
    }]
    save_json("packing", {
        "n_workloads": n_w, "n_configs": N_CONFIGS, "workloads": names,
        "scale": SIM_SCALE, "max_cycles": max_cycles,
        "plan": plan.describe(), "n_buckets": len(groups),
        "buckets": [[names[i] for i in g] for g in groups],
        "t_loop_s": t_loop, "t_monolithic_s": t_mono,
        "t_bucketed_s": t_buck,
        "compile_cold_s": cold_compile, "compile_warm_s": warm_compile,
        "speedup": speedup_vs_loop,
        "speedup_monolithic": t_loop / t_mono,
        "donation": {
            "state_mb": state_mb, "live_donate_mb": donate_live,
            "live_nodonate_mb": nodonate_live,
            "freed": donation_freed, "bit_exact": bit_exact,
        },
    })
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
