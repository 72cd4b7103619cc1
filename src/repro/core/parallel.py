"""Execution modes for the SM phase — the paper's `#pragma omp parallel for`.

  'seq'   — lax.map over SMs: one SM at a time (single-thread reference)
  'vmap'  — vectorized over the SM axis (single-chip SIMD parallelism)
  'shard' — shard_map over an 'sm' device mesh axis: each device simulates
            its SM shard; the serial region (memory system + CTA dispatch)
            is computed REPLICATED from an all-gathered request table, which
            preserves sequential semantics bit-exactly at any device count.

SM→device assignment ("OpenMP scheduler" analogue):
  'static'  — contiguous SM blocks per device
  'dynamic' — deterministic load-aware deal: SMs dealt round-robin so early
              (CTA-heavy under round-robin dispatch) SMs spread evenly.
Both are pure relabelings of the SM axis — simulation results are identical;
only per-device work balance changes (reported by benchmarks/scheduler.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import telemetry
from repro.core.engine import (CTA_ISSUE, LOOP_CONTROL, MEM_PHASE, SM_PHASE,
                               converged, mark_entry_converged)
from repro.sim.config import GPUConfig, split_config, static_part
from repro.sim.cta import cta_issue
from repro.sim.memsys import mem_phase
from repro.sim.smcore import sm_quantum_single


def make_sm_runner(cfg, mode: str = "vmap", mesh: Mesh = None):
    """Returns sm_runner(warp, sm, req, stats_sm, trace, t0, dyn).

    cfg may be a full GPUConfig or just its StaticConfig half — only static
    shape fields are closed over; all timing numerics flow in via ``dyn``
    (the typed DynConfig pytree — replicated under shard_map, vmapped over
    lanes by core/sweep.py; the spec/tree plumbing below is pytree-generic
    so the grouped, table-valued leaves need no special casing).

    mode='shard' needs a ``mesh`` with an 'sm' axis: the SM phase runs
    under shard_map over that axis (each device vmaps its SM block), while
    the serial region stays on the full replicated arrays in
    ``engine.quantum_step`` — one entry point for every execution mode.
    For the fully sharded quantum (serial region recomputed replicated
    from an all-gather inside the shard region) see
    ``make_sharded_quantum`` / ``core/distribute.py``.
    """
    scfg = static_part(cfg)

    if mode == "vmap":
        def runner(warp, sm, req, stats_sm, trace, t0, dyn):
            return jax.vmap(
                lambda w, s, r, st: sm_quantum_single(
                    w, s, r, st, trace, t0, scfg, dyn))(
                warp, sm, req, stats_sm)
        return runner

    if mode == "seq":
        def runner(warp, sm, req, stats_sm, trace, t0, dyn):
            return jax.lax.map(
                lambda a: sm_quantum_single(a[0], a[1], a[2], a[3], trace,
                                            t0, scfg, dyn),
                (warp, sm, req, stats_sm))
        return runner

    if mode == "shard":
        if mesh is None or "sm" not in mesh.axis_names:
            raise ValueError(
                "mode='shard' needs mesh= with an 'sm' axis, e.g. "
                "make_sm_runner(cfg, 'shard', make_host_mesh(n, 'sm'))")
        if len(mesh.axis_names) > 1:
            # Slice out a 1-D ('sm',) submesh: a shard_map whose specs
            # never mention some mesh axis mis-replicates across compiled
            # loop iterations under check_vma=False (the replication claim
            # is trusted, not checked), so this runner — whose loop lives
            # OUTSIDE the shard region in engine.quantum_step — must own
            # every axis of the mesh it runs on.  Lane-parallel execution
            # over a full 2-D ('cfg', 'sm') mesh is core/distribute.py's
            # job, where the whole loop sits inside one shard_map.
            axis = mesh.axis_names.index("sm")
            devs = mesh.devices[tuple(
                slice(None) if i == axis else 0
                for i in range(mesh.devices.ndim))]
            mesh = Mesh(devs, ("sm",))

        n_dev = mesh.shape["sm"]
        if scfg.n_sm % n_dev:
            raise ValueError(
                f"n_sm={scfg.n_sm} not divisible by mesh 'sm' axis "
                f"size {n_dev}")
        sm_spec, rep = P("sm"), P()

        def spec_like(tree, spec):
            return jax.tree_util.tree_map(lambda _: spec, tree)

        def runner(warp, sm, req, stats_sm, trace, t0, dyn):
            def local(warp, sm, req, stats_sm, trace, t0, dyn):
                return jax.vmap(
                    lambda w, s, r, st: sm_quantum_single(
                        w, s, r, st, trace, t0, scfg, dyn))(
                    warp, sm, req, stats_sm)

            parts = (warp, sm, req, stats_sm)
            in_specs = tuple(spec_like(p, sm_spec) for p in parts) + (
                spec_like(trace, rep), rep, spec_like(dyn, rep))
            out_specs = tuple(spec_like(p, sm_spec) for p in parts)
            fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            return fn(warp, sm, req, stats_sm, trace, t0, dyn)
        return runner

    raise ValueError(f"unknown mode {mode!r} (expected seq/vmap/shard)")


def make_shard_body(cfg, n_dev: int, exchange: str = "window"):
    """The per-device quantum step for SM-axis sharding — a plain traced
    function of LOCAL shards, written against mesh axis name 'sm'.

    ``body(warp, sm, req, stats_sm, mem, ctrl, gstats, trace, dyn)`` where
    warp/sm/req/stats_sm hold this device's SM block (n_sm // n_dev rows)
    and mem/ctrl/gstats/trace/dyn are replicated.  The serial region
    all-gathers the (small) request table and warp arrays over 'sm',
    computes identical results on every device, and each device then runs
    its SM shard locally for Δ cycles.

    Factored out of ``make_sharded_quantum`` so the same body serves the
    1-D ('sm',) mesh (below) and the 2-D ('cfg', 'sm') mesh
    (core/distribute.py), where it additionally runs vmapped over the
    device-local config lanes — collectives stay per-'sm'-group, so each
    lane remains bit-identical to its solo run.
    """
    scfg = static_part(cfg)
    assert scfg.n_sm % n_dev == 0, (scfg.n_sm, n_dev)
    chunk = scfg.n_sm // n_dev

    def body(warp, sm, req, stats_sm, mem, ctrl, gstats, trace, dyn):
        t0 = ctrl["cycle"]
        # --- serial region, replicated ---------------------------------
        req_f = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, "sm", axis=0, tiled=True), req)
        warp_f = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, "sm", axis=0, tiled=True), warp)
        with jax.named_scope(MEM_PHASE):
            req_f, mem, gstats = mem_phase(req_f, mem, gstats, t0, scfg,
                                           dyn, sm_ids=ctrl["sm_ids"])
        with jax.named_scope(CTA_ISSUE):
            warp_f, ctrl, gstats = cta_issue(warp_f, dict(ctrl), gstats,
                                             trace, scfg)
        i = jax.lax.axis_index("sm")
        take = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, i * chunk, chunk, axis=0)
        req_l = jax.tree_util.tree_map(take, req_f)
        warp_l = jax.tree_util.tree_map(take, warp_f)
        # --- parallel region: my SM shard ------------------------------
        with jax.named_scope(SM_PHASE):
            warp_l, sm, req_l, stats_sm = sm_shard(warp_l, sm, req_l,
                                                   stats_sm, trace, t0, dyn)
        # --- done detection (replicated) --------------------------------
        with jax.named_scope(LOOP_CONTROL):
            cycle_end = t0 + scfg.quantum
            done = converged(ctrl, warp_l, req_l, trace, axis_name="sm")
            done_cycle = jnp.where((ctrl["done_cycle"] < 0) & done,
                                   cycle_end, ctrl["done_cycle"])
            ctrl = dict(ctrl, cycle=cycle_end, done_cycle=done_cycle)
        return warp_l, sm, req_l, stats_sm, mem, ctrl, gstats

    def sm_shard(warp_l, sm, req_l, stats_sm, trace, t0, dyn):
        if exchange == "cycle":
            # emulate a per-cycle barrier: gather the table every cycle
            from repro.sim.smcore import sm_cycle_single

            def cyc(i, carry):
                warp_l, sm, req_l, stats_sm, dbg = carry
                warp_l, sm, req_l, stats_sm = jax.vmap(
                    lambda w, s, r, st: sm_cycle_single(
                        w, s, r, st, trace, t0 + i, scfg, dyn))(
                    warp_l, sm, req_l, stats_sm)
                gathered = jax.lax.all_gather(req_l["stage"], "sm", axis=0,
                                              tiled=True)
                dbg = dbg + jnp.sum(gathered, dtype=jnp.int32) * 0
                return warp_l, sm, req_l, stats_sm, dbg

            warp_l, sm, req_l, stats_sm, _ = jax.lax.fori_loop(
                0, scfg.quantum, cyc,
                (warp_l, sm, req_l, stats_sm, jnp.zeros((), jnp.int32)))
            return warp_l, sm, req_l, stats_sm
        return jax.vmap(
            lambda w, s, r, st: sm_quantum_single(w, s, r, st, trace, t0,
                                                  scfg, dyn))(
            warp_l, sm, req_l, stats_sm)

    return body


def make_sharded_quantum(cfg: GPUConfig, mesh: Mesh,
                         exchange: str = "window"):
    """The whole quantum step under shard_map (engine.quantum_step analogue).

    Per-SM arrays are sharded over the 'sm' axis; mem/ctrl/global-stats are
    replicated — see ``make_shard_body`` for the per-device step.

    exchange='window' — one all-gather per quantum (the lookahead window,
    beyond-paper optimization).  exchange='cycle' — additionally all-gathers
    every inner cycle, emulating the paper's per-cycle OpenMP barrier;
    results are bit-identical, only communication frequency differs.
    """
    n_dev = mesh.shape["sm"]
    body = make_shard_body(cfg, n_dev, exchange)

    sm_spec = P("sm")
    rep = P()

    def spec_like(tree, spec):
        return jax.tree_util.tree_map(lambda _: spec, tree)

    def sharded_step(state, trace, dyn):
        in_specs = (spec_like(state["warp"], sm_spec),
                    spec_like(state["sm"], sm_spec),
                    spec_like(state["req"], sm_spec),
                    spec_like(state["stats_sm"], sm_spec),
                    spec_like(state["mem"], rep),
                    spec_like(state["ctrl"], rep),
                    spec_like(state["stats"], rep),
                    spec_like(trace, rep),
                    spec_like(dyn, rep))
        out_specs = in_specs[:7]
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        warp, sm, req, stats_sm, mem, ctrl, gstats = fn(
            state["warp"], state["sm"], state["req"], state["stats_sm"],
            state["mem"], state["ctrl"], state["stats"], trace, dyn)
        out = {"warp": warp, "sm": sm, "req": req, "mem": mem,
               "ctrl": ctrl, "stats_sm": stats_sm, "stats": gstats}
        # telemetry runs OUTSIDE the shard region, where the out_specs
        # have reassembled the full per-SM arrays — no collectives needed
        if "telem" in state:
            with jax.named_scope(LOOP_CONTROL):
                out["telem"] = telemetry.quantum_update(
                    state["telem"], out, trace, static_part(cfg))
        return out

    return sharded_step


def run_kernel_sharded(state, trace, cfg: GPUConfig, mesh: Mesh,
                       max_cycles: int = 1 << 20, exchange: str = "window",
                       dyn: dict = None, early_exit: bool = True):
    if dyn is None:
        _, dyn = split_config(cfg)
    step = make_sharded_quantum(cfg, mesh, exchange)

    def cond(st):
        with jax.named_scope(LOOP_CONTROL):
            return (st["ctrl"]["done_cycle"] < 0) & \
                (st["ctrl"]["cycle"] < max_cycles)

    def body(st):
        return step(st, trace, dyn)

    if early_exit:
        # state here holds the FULL per-SM arrays (out_specs reassemble
        # outside the shard region), so no collective is needed
        with jax.named_scope(LOOP_CONTROL):
            state = mark_entry_converged(state, trace)
    state = jax.lax.while_loop(cond, body, state)
    if "telem" in state:
        with jax.named_scope(LOOP_CONTROL):
            state = dict(state, telem=telemetry.sample(
                state["telem"], state, static_part(cfg), force=True))
    return state


# ---------------------------------------------------------------------------
# SM→device assignment (the OpenMP scheduler analogue)
# ---------------------------------------------------------------------------

def sm_permutation(cfg: GPUConfig, n_devices: int,
                   policy: str = "static") -> np.ndarray:
    sms = np.arange(cfg.n_sm)
    if policy == "static":
        return sms
    if policy == "dynamic":
        # deal SMs round-robin to devices, then concatenate per-device lists
        per_dev = [sms[d::n_devices] for d in range(n_devices)]
        return np.concatenate(per_dev)
    raise ValueError(policy)


def permute_state(state: dict, perm: np.ndarray) -> dict:
    """Relabel the SM axis: array position p now holds SM ``perm[p]``.
    ctrl.sm_ids records the original ids so CTA dispatch (round-robin over
    original ids) is invariant — only the device placement changes."""
    idx = jnp.asarray(perm, jnp.int32)
    out = dict(state)
    for part in ("warp", "sm", "req", "stats_sm"):
        out[part] = jax.tree_util.tree_map(lambda x: x[idx], state[part])
    out["ctrl"] = dict(state["ctrl"], sm_ids=state["ctrl"]["sm_ids"][idx])
    return out
