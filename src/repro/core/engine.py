"""Deterministic simulation engine: quantum loop (Algorithm 1, windowed).

Each machine quantum (Δ=16 cycles):
  1. memory phase   (serial region, lines 8–19)   — full request table
  2. CTA dispatch   (serial region, line 25)      — quantum boundary
  3. SM phase ×Δ    (parallel region, lines 20–23) — per-SM, local

The SM phase runner is injected (core/parallel.py) so the same engine body
serves the sequential, vectorized and sharded execution modes — results are
bit-identical by construction (tests/test_sim_determinism.py).

Config threading: the engine takes the hashable ``StaticConfig`` (jit-static
shapes) and the typed ``DynConfig`` pytree of traced timing parameters
separately.  All timing numerics — scalar latencies AND the per-class
``core.lat``/``core.disp`` tables — enter the compiled program as
*arguments*, never as Python constants, so ``core/sweep.py`` can vmap the
whole engine over a batch of dynamic configs (one design-space-exploration
lane per config, ~20+ sweepable entries each).

Phase scopes: every execution mode runs the quantum's phases under the
``jax.named_scope`` names in ``PHASES``, so each instruction of the
compiled program carries its phase in the ``op_name`` of its metadata
and a profile splits device time by phase.  Scopes change metadata
only: the compiled program is otherwise the same.

Kernel threading: a workload's kernels are padded + stacked
(core/batch.py) and run by a ``lax.scan`` over the kernel axis
(``run_workload_stacked``) — the whole workload is ONE traced program, so
``core/sweep.py:grid_sweep`` can additionally vmap over a stacked batch
of *workloads* (benchmarks × configs in one compiled call).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.sim.config import DynConfig, GPUConfig, StaticConfig, split_config
from repro.sim.cta import cta_issue
from repro.sim.memsys import mem_phase
from repro.sim.state import init_state, reset_for_kernel
from repro.sim.trace import Workload

# the quantum loop's phases, as named scopes in the compiled program's
# op_name metadata: memory phase, CTA dispatch, the SM runner, and the
# loop's own bookkeeping (convergence, clock, per-kernel reset, cycle and
# timeout accounting, telemetry)
PHASES = MEM_PHASE, CTA_ISSUE, SM_PHASE, LOOP_CONTROL = (
    "sim.mem_phase", "sim.cta_issue", "sim.sm_phase", "sim.loop_control")


def converged(ctrl: dict, warp: dict, req: dict, trace: dict,
              axis_name=None):
    """The ONE kernel-completion predicate every execution mode shares:
    all CTAs dispatched, no live warp (active with work left or loads
    pending), no in-flight memory request.  Pass ``axis_name`` when warp/
    req hold only this device's SM shard — the counts psum over that mesh
    axis so every device sees the full-machine verdict."""
    live = warp["active"] & ~((warp["pc"] >= trace["n_instr"])
                              & (warp["pending"] == 0))
    n_live = jnp.sum(live, dtype=jnp.int32)
    n_busy = jnp.sum(jnp.asarray(req["stage"] != 0), dtype=jnp.int32)
    if axis_name is not None:
        n_live = jax.lax.psum(n_live, axis_name)
        n_busy = jax.lax.psum(n_busy, axis_name)
    return (ctrl["next_cta"] >= trace["n_ctas"]) & (n_live == 0) & \
        (n_busy == 0)


def mark_entry_converged(state: dict, trace: dict, axis_name=None) -> dict:
    """Early-exit: stamp ``done_cycle`` BEFORE the quantum while_loop when
    the kernel is already converged at entry, so the loop runs ZERO
    iterations instead of burning one full quantum discovering it.

    After ``reset_for_kernel`` only an ``n_ctas == 0`` padding kernel can
    be entry-converged (``next_cta`` starts at 0, so any real kernel still
    has CTAs to dispatch) — and the workload scan masks those kernels'
    state and cycles out entirely — so this is bit-exact by construction.
    The savings are real though: every empty slot a short workload padded
    up to the grid's kernel count previously cost a full quantum_step
    (serial region + Δ SM cycles + collectives on the distributed path).
    """
    entry = converged(state["ctrl"], state["warp"], state["req"], trace,
                      axis_name)
    dc = jnp.where((state["ctrl"]["done_cycle"] < 0) & entry,
                   state["ctrl"]["cycle"], state["ctrl"]["done_cycle"])
    return dict(state, ctrl=dict(state["ctrl"], done_cycle=dc))


def quantum_step(state: dict, trace: dict, cfg: StaticConfig,
                 dyn: DynConfig, sm_runner):
    t0 = state["ctrl"]["cycle"]
    with jax.named_scope(MEM_PHASE):
        req, mem, gstats = mem_phase(state["req"], state["mem"],
                                     state["stats"], t0, cfg, dyn,
                                     sm_ids=state["ctrl"]["sm_ids"])
    with jax.named_scope(CTA_ISSUE):
        warp, ctrl, gstats = cta_issue(state["warp"], dict(state["ctrl"]),
                                       gstats, trace, cfg)
    with jax.named_scope(SM_PHASE):
        warp, sm, req, stats_sm = sm_runner(warp, state["sm"], req,
                                            state["stats_sm"], trace, t0,
                                            dyn)
    with jax.named_scope(LOOP_CONTROL):
        cycle_end = t0 + cfg.quantum
        done = converged(ctrl, warp, req, trace)
        done_cycle = jnp.where((ctrl["done_cycle"] < 0) & done, cycle_end,
                               ctrl["done_cycle"])
        ctrl = dict(ctrl, cycle=cycle_end, done_cycle=done_cycle)
        out = {"warp": warp, "sm": sm, "req": req, "mem": mem, "ctrl": ctrl,
               "stats_sm": stats_sm, "stats": gstats}
        # opt-in counter timeline: statically gated, so the compiled
        # program is unchanged when telemetry is off (core/telemetry.py)
        if telemetry.enabled(cfg):
            out["telem"] = telemetry.quantum_update(state["telem"], out,
                                                    trace, cfg)
    return out


def run_kernel(state: dict, trace: dict, cfg: StaticConfig,
               dyn: DynConfig, sm_runner, max_cycles: int = 1 << 20,
               early_exit: bool = True):
    def cond(st):
        with jax.named_scope(LOOP_CONTROL):
            return (st["ctrl"]["done_cycle"] < 0) & \
                (st["ctrl"]["cycle"] < max_cycles)

    def body(st):
        return quantum_step(st, trace, cfg, dyn, sm_runner)

    if early_exit:
        with jax.named_scope(LOOP_CONTROL):
            state = mark_entry_converged(state, trace)
    state = jax.lax.while_loop(cond, body, state)
    # force a final snapshot per kernel so the last written timeline row
    # always equals the final cumulative counters (core/telemetry.py)
    if telemetry.enabled(cfg):
        with jax.named_scope(LOOP_CONTROL):
            state = dict(state, telem=telemetry.sample(
                state["telem"], state, cfg, force=True))
    return state


def kernel_cycles(ctrl: dict):
    """Cycles charged to the kernel that just ran: its done_cycle, or the
    current clock if it hit max_cycles.  The ONE accounting rule every
    execution mode shares (solo, vmapped sweep, sharded)."""
    return jnp.where(ctrl["done_cycle"] >= 0, ctrl["done_cycle"],
                     ctrl["cycle"])


def run_workload_stacked(state: dict, stacked: dict, cfg: StaticConfig,
                         dyn: DynConfig, sm_runner, max_cycles: int = 1 << 20,
                         state_transform=None, kernel_runner=None,
                         early_exit: bool = True) -> dict:
    """Run a whole workload as ONE traced program: ``lax.scan`` over the
    stacked kernel axis (core/batch.py:stack_kernels).

    Per scan step: traced state reset (sim/state.py:reset_for_kernel),
    run the kernel to completion, accumulate its cycles.  Padding kernels
    (``n_ctas == 0``) are masked out — the carried state passes through
    unchanged and 0 cycles are charged — so a workload padded to a shared
    kernel count is bit-identical to its unpadded self.  With
    ``early_exit`` (default) those padding kernels also cost ~zero WORK:
    they are converged at entry, so the quantum while_loop runs zero
    iterations (``mark_entry_converged``) instead of one full quantum.
    A kernel that hits ``max_cycles`` (``done_cycle`` still < 0) bumps
    the ``timeouts`` counter so truncated runs are reported, not silently
    counted as complete (core/stats.py:finalize → ``timeout``).

    The stacked trace may be in either layout (core/batch.py): padded —
    every leaf has leading kernel axis — or RAGGED (``instr_base``
    present) — per-kernel scalars scan while the flat concatenated
    instruction streams are closed over and re-merged per step, so short
    kernels stop paying for the longest kernel's NOP slots.

    Being a single traced function of (state, stacked, dyn), this is what
    ``core/sweep.py`` vmaps over workload and config lanes.

    ``kernel_runner`` — ``(state, packed, dyn) -> state`` — substitutes the
    default ``run_kernel`` quantum loop with a custom traced one (e.g. the
    SM-sharded step of core/distribute.py, where ``state``'s per-SM arrays
    hold only this device's shard and ``cfg`` is the matching local-shape
    StaticConfig).  The scan, per-kernel reset, empty-kernel masking and
    timeout accounting stay shared across every execution mode.
    """
    zero = jnp.zeros((), jnp.int32)
    ragged = "instr_base" in stacked
    if ragged:
        from repro.core.batch import split_ragged
        scan_xs, flat = split_ragged(stacked)
    else:
        scan_xs, flat = stacked, {}

    def body(carry, scanned):
        prev, total, timeouts = carry
        with jax.named_scope(LOOP_CONTROL):
            packed = dict(flat, **scanned) if ragged else scanned
            st = reset_for_kernel(prev, cfg)
            if state_transform is not None:
                st = state_transform(st)
        if kernel_runner is None:
            st = run_kernel(st, packed, cfg, dyn, sm_runner, max_cycles,
                            early_exit)
        else:
            st = kernel_runner(st, packed, dyn)
        with jax.named_scope(LOOP_CONTROL):
            empty = packed["n_ctas"] == 0
            total = total + jnp.where(empty, 0, kernel_cycles(st["ctrl"]))
            timeouts = timeouts + jnp.where(
                ~empty & (st["ctrl"]["done_cycle"] < 0), 1, 0)
            nxt = jax.tree_util.tree_map(
                lambda old, new: jnp.where(empty, old, new), prev, st)
        return (nxt, total, timeouts), None

    (state, total, timeouts), _ = jax.lax.scan(
        body, (state, zero, zero), scan_xs)
    return dict(state, ctrl=dict(state["ctrl"], total_cycles=total,
                                 timeouts=timeouts))


def run_workload(state: dict, kernels: list, cfg: StaticConfig,
                 dyn: DynConfig, sm_runner=None, max_cycles: int = 1 << 20,
                 state_transform=None, kernel_runner=None) -> dict:
    """Run packed kernels back-to-back, accumulating total cycles.

    Default path: the kernel list is padded + stacked (core/batch.py) and
    handed to ``run_workload_stacked`` — one ``lax.scan``, one compiled
    kernel body regardless of kernel count; a pure traced function of
    (state, dyn) that core/sweep.py jits/vmaps whole.  Pass
    ``kernel_runner`` — ``(state, packed, dyn) -> state`` — to substitute
    a pre-jitted or sharded per-kernel step; that path keeps the host
    loop (per-kernel device programs) but shares the same accounting,
    including the ``timeouts`` truncation counter.
    """
    if kernel_runner is None:
        from repro.core.batch import stack_kernels
        return run_workload_stacked(state, stack_kernels(kernels), cfg, dyn,
                                    sm_runner, max_cycles, state_transform)
    total_cycles = jnp.zeros((), jnp.int32)
    timeouts = jnp.zeros((), jnp.int32)
    for packed in kernels:
        state = reset_for_kernel(state, cfg)
        if state_transform is not None:
            state = state_transform(state)
        state = kernel_runner(state, packed, dyn)
        total_cycles = total_cycles + kernel_cycles(state["ctrl"])
        timeouts = timeouts + jnp.where(state["ctrl"]["done_cycle"] < 0,
                                        1, 0)
    state["ctrl"]["total_cycles"] = total_cycles
    state["ctrl"]["timeouts"] = timeouts
    return state


def build_simulation(workload: Workload, cfg: GPUConfig, sm_runner,
                     plan=None, state_transform=None):
    """Build the one traced program of a whole-workload run without
    calling it: returns ``(run, scfg, dyn)``, and
    ``run(init_state(scfg), dyn)`` is the final state.

    The whole workload — per-kernel reset, every kernel's quantum loop —
    is one traced program (``lax.scan`` over the stacked kernel axis);
    the kernel trace is closed over, the initial state and the typed
    ``DynConfig`` are arguments, and it is jitted with the state donated.
    Kept apart from ``simulate`` so callers can lower and compile it
    separately from executing it (core/sweep.py:timed_call) or for a
    described device."""
    from repro.core.batch import (check_workload_fits, concat_kernels,
                                  stack_kernels)
    from repro.core.plan import RunPlan

    plan = plan if plan is not None else RunPlan()
    plan.activate_caches()
    scfg, dyn = split_config(cfg)
    check_workload_fits(scfg, workload)
    packs = [k.pack() for k in workload.kernels]
    stacked = (concat_kernels(packs) if plan.layout == "ragged"
               else stack_kernels(packs))

    def run(state0, d):
        return run_workload_stacked(state0, stacked, scfg, d,
                                    sm_runner, plan.max_cycles,
                                    state_transform,
                                    early_exit=plan.early_exit)

    # the freshly-built initial state is argument 0 and DONATED: the
    # final state aliases its buffers instead of holding two copies
    return jax.jit(run, donate_argnums=(0,)), scfg, dyn


def simulate(workload: Workload, cfg: GPUConfig, sm_runner,
             max_cycles: int = None, state_transform=None,
             plan=None) -> dict:
    """Run all kernels of a workload; returns the final state
    (``build_simulation``, then one call of it).

    Execution knobs (max_cycles, early_exit, trace layout, cache dir)
    come from ``plan=`` (core/plan.py:RunPlan); the bare ``max_cycles=``
    keyword still works for one release via the deprecation shim."""
    from repro.core.plan import resolve_plan

    plan = resolve_plan(plan, where="simulate", max_cycles=max_cycles)
    run, scfg, dyn = build_simulation(workload, cfg, sm_runner, plan,
                                      state_transform)
    return run(init_state(scfg), dyn)
