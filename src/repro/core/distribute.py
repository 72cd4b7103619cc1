"""2-D ('cfg', 'sm') mesh distribution — sweeps across devices.

PR 1/2 made the benchmarks × configs grid ONE compiled program
(core/sweep.py), but every lane still lived on one device; the SM-axis
sharding (core/parallel.py) conversely knew nothing about lanes.  This
module unifies the two behind one mesh abstraction:

  · the lane axis of ``sweep()`` / ``grid_sweep()`` is sharded over the
    mesh's **'cfg'** axis — config lanes are perfectly independent, so
    this needs NO communication (ScaleSimulator's near-linear regime);
  · within each lane, the SM axis is sharded over the **'sm'** axis using
    the same per-device quantum body as the 1-D shard mode
    (core/parallel.py:make_shard_body): the serial region is recomputed
    REPLICATED from an all-gather over 'sm', which preserves sequential
    semantics bit-exactly.

Each device therefore simulates its (config-shard × SM-shard) block, and
every lane is bit-identical to its solo single-device run at ANY mesh
shape — 1×N, N×1, A×B (tests/test_mesh_sweep.py).  The lane-stacked
dynamic pytree placed over 'cfg' is the typed ``DynConfig``: its scalar
leaves shard as (n_lanes,) and the per-class ``core.lat``/``core.disp``
tables as (n_lanes, N_CLASSES) — ``P('cfg')`` touches only the leading
lane axis, so table-valued sweeps distribute exactly like scalar ones.  All simulator state is
int32, so there is no floating-point reassociation to worry about either.

CPU recipe (jax locks the device count at first init, so set this before
importing jax — or use the subprocess helpers in benchmarks/):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m repro.launch.zoo --grid 4 4 --mesh 2 2 --check
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import telemetry
from repro.core.engine import (LOOP_CONTROL, mark_entry_converged,
                               run_workload_stacked)
from repro.core.parallel import make_shard_body
from repro.sim.config import StaticConfig, static_part

CFG_AXIS, SM_AXIS = "cfg", "sm"

# state parts with a leading n_sm axis (sharded over 'sm'); the rest —
# mem/ctrl/stats — are replicated within an 'sm' group (sim/state.py).
SHARDED_PARTS = ("warp", "sm", "req", "stats_sm")
STATE_PARTS = ("warp", "sm", "req", "mem", "ctrl", "stats_sm", "stats")


def make_mesh(n_cfg: int, n_sm: int = 1) -> Mesh:
    """2-D ('cfg', 'sm') device mesh over the first n_cfg × n_sm devices.

    Either axis may be 1 (1×N = pure SM sharding, N×1 = pure lane
    sharding), so one mesh type serves every distribution shape.
    """
    need = n_cfg * n_sm
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh ({n_cfg}, {n_sm}) needs {need} devices, have "
            f"{len(devices)} — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "in the environment before jax initializes.")
    return Mesh(np.asarray(devices[:need]).reshape(n_cfg, n_sm),
                (CFG_AXIS, SM_AXIS))


def state_specs(*prefix, telem: bool = False) -> dict:
    """PartitionSpec pytree-prefix for a state dict whose leaves carry
    ``prefix`` leading lane axes: per-SM parts additionally shard their SM
    axis over 'sm'; mem/ctrl/stats are replicated within an 'sm' group.
    ``telem`` adds the replicated counter-timeline part present when the
    StaticConfig enables telemetry (core/telemetry.py)."""
    parts = STATE_PARTS + (("telem",) if telem else ())
    return {k: (P(*prefix, SM_AXIS) if k in SHARDED_PARTS else P(*prefix))
            for k in parts}


def check_mesh(mesh: Mesh, scfg: StaticConfig, n_lanes: int) -> None:
    if set(mesh.axis_names) != {CFG_AXIS, SM_AXIS}:
        raise ValueError(
            f"sweep mesh must have axes ('{CFG_AXIS}', '{SM_AXIS}'), got "
            f"{mesh.axis_names} (build one with core.distribute.make_mesh)")
    if n_lanes % mesh.shape[CFG_AXIS]:
        raise ValueError(
            f"{n_lanes} config lanes not divisible by mesh '{CFG_AXIS}' "
            f"axis size {mesh.shape[CFG_AXIS]}")
    if scfg.n_sm % mesh.shape[SM_AXIS]:
        raise ValueError(
            f"n_sm={scfg.n_sm} not divisible by mesh '{SM_AXIS}' axis "
            f"size {mesh.shape[SM_AXIS]}")


def place_lanes(tree, mesh: Mesh, spec: P = None):
    """Place a lane-stacked pytree with an explicit NamedSharding (leading
    lane axis over 'cfg' by default) instead of leaving it to implicit
    single-device placement + transfer at dispatch."""
    sh = NamedSharding(mesh, spec if spec is not None else P(CFG_AXIS))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def place_state(state: dict, mesh: Mesh, *prefix) -> dict:
    """Place a host-built batched initial state (core/sweep.py:
    batched_init) with the same per-part shardings the dist runners
    produce (``state_specs``): per-SM parts sharded ('sm' blocks match
    the contiguous slices the old in-region ``local_init`` took), the
    rest replicated within an 'sm' group.  Placing the state OUTSIDE the
    compiled program lets the runners DONATE it — the final state aliases
    these buffers instead of allocating a second full copy."""
    specs = state_specs(*prefix, telem="telem" in state)
    return {k: jax.tree_util.tree_map(
                lambda x, s=specs[k]: jax.device_put(
                    x, NamedSharding(mesh, s)), v)
            for k, v in state.items()}


def make_dist_kernel_runner(scfg: StaticConfig, n_sm_dev: int,
                            exchange: str = "window",
                            max_cycles: int = 1 << 20,
                            early_exit: bool = True):
    """Per-lane kernel quantum loop on LOCAL SM shards — the sharded
    analogue of ``engine.run_kernel``, pluggable into
    ``run_workload_stacked(kernel_runner=...)``."""
    body = make_shard_body(scfg, n_sm_dev, exchange)
    telem_on = telemetry.enabled(scfg)

    def kernel_runner(st, packed, dyn):
        def cond(s):
            with jax.named_scope(LOOP_CONTROL):
                return (s["ctrl"]["done_cycle"] < 0) & \
                    (s["ctrl"]["cycle"] < max_cycles)

        def step(s):
            warp, sm, req, stats_sm, mem, ctrl, gstats = body(
                s["warp"], s["sm"], s["req"], s["stats_sm"],
                s["mem"], s["ctrl"], s["stats"], packed, dyn)
            out = {"warp": warp, "sm": sm, "req": req, "mem": mem,
                   "ctrl": ctrl, "stats_sm": stats_sm, "stats": gstats}
            if telem_on:
                # per-SM arrays here are this device's shard — the counter
                # sums psum over 'sm' so the replicated buffer row holds
                # full-machine totals, bit-identical on every device
                with jax.named_scope(LOOP_CONTROL):
                    out["telem"] = telemetry.quantum_update(
                        s["telem"], out, packed, scfg, axis_name=SM_AXIS)
            return out

        if early_exit:
            # entry check runs BEFORE the loop (collectives are illegal in
            # a while_loop cond); warp/req are local shards, so the live/
            # busy counts psum over 'sm' — every device agrees, and an
            # empty padding kernel skips its quantum (all-gathers included)
            with jax.named_scope(LOOP_CONTROL):
                st = mark_entry_converged(st, packed, axis_name=SM_AXIS)
        st = jax.lax.while_loop(cond, step, st)
        if telem_on:
            with jax.named_scope(LOOP_CONTROL):
                st = dict(st, telem=telemetry.sample(
                    st["telem"], st, scfg, axis_name=SM_AXIS, force=True))
        return st

    return kernel_runner


def _make_lane_runner(scfg: StaticConfig, n_sm_dev: int, exchange: str,
                      max_cycles: int, early_exit: bool = True):
    """One (workload × config) lane, run on this device's SM shard.  The
    kernel-axis scan / reset / timeout accounting is the SHARED engine path
    (run_workload_stacked) — only the per-kernel quantum loop is swapped
    for the sharded one, with a local-shape StaticConfig so the traced
    reset builds shard-sized per-SM arrays."""
    chunk = scfg.n_sm // n_sm_dev
    local_scfg = dataclasses.replace(scfg, n_sm=chunk)
    kernel_runner = make_dist_kernel_runner(scfg, n_sm_dev, exchange,
                                            max_cycles, early_exit)

    def run_lane(st, stacked, dyn):
        # st arrives pre-sharded by the shard_map in_specs: per-SM parts
        # hold this device's contiguous SM block (the same slice the old
        # in-region local_init took via axis_index), ctrl keeps the FULL
        # sm_ids table — the serial region is computed replicated and CTA
        # round-robin follows original ids
        return run_workload_stacked(st, stacked, local_scfg, dyn, None,
                                    max_cycles, kernel_runner=kernel_runner)

    return run_lane


def make_dist_sweep_runner(scfg: StaticConfig, mesh: Mesh,
                           max_cycles: int = 1 << 20,
                           exchange: str = "window",
                           early_exit: bool = True):
    """One compiled program for a config sweep on a ('cfg', 'sm') mesh:
    ``(state_batch, stacked_kernels, dyn_batch) -> batched final
    state``.  Lanes are sharded over 'cfg' (vmap over the device-local
    lanes inside the shard region); each lane's SM axis is sharded over
    'sm'.  The initial state batch (placed by ``place_state``) is
    DONATED — in and out shardings match part-by-part, so the final
    state aliases the input buffers on every device."""
    scfg = static_part(scfg)
    run_lane = _make_lane_runner(scfg, mesh.shape[SM_AXIS], exchange,
                                 max_cycles, early_exit)
    specs = state_specs(CFG_AXIS, telem=telemetry.enabled(scfg))

    def body(state, stacked, dyn_batch):
        return jax.vmap(run_lane, in_axes=(0, None, 0))(
            state, stacked, dyn_batch)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(specs, P(), P(CFG_AXIS)),
                       out_specs=specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def make_dist_grid_runner(scfg: StaticConfig, mesh: Mesh,
                          max_cycles: int = 1 << 20,
                          exchange: str = "window",
                          early_exit: bool = True):
    """One compiled program for a whole (workload × config) grid on a
    ('cfg', 'sm') mesh — the distributed twin of
    ``core/sweep.py:make_grid_runner``.  The workload axis is replicated
    (every device runs all W workloads for ITS config lanes); the config
    axis is sharded over 'cfg', the SM axis over 'sm'.  The (W, C)
    initial state batch is DONATED, same as the sweep runner."""
    scfg = static_part(scfg)
    run_lane = _make_lane_runner(scfg, mesh.shape[SM_AXIS], exchange,
                                 max_cycles, early_exit)
    specs = state_specs(None, CFG_AXIS, telem=telemetry.enabled(scfg))

    def body(state, stacked, dyn_batch):
        over_cfgs = jax.vmap(run_lane, in_axes=(0, None, 0))
        return jax.vmap(over_cfgs, in_axes=(0, 0, None))(
            state, stacked, dyn_batch)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(specs, P(), P(CFG_AXIS)),
                       out_specs=specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))
