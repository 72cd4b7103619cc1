"""Batched design-space exploration: vmap the WHOLE simulator over configs.

The tentpole consequence of the static/dynamic config split (sim/config.py):
every timing parameter — scalar latencies AND the typed ``DynConfig``'s
per-class ``core.lat``/``core.disp`` tables — reaches the compiled engine as
a traced argument, so a sweep over N candidate configs that share one
``StaticConfig`` shape is a single ``jit(vmap(run_workload))`` — one XLA
program, one compilation, all lanes advancing together on one chip.  Each vmap lane is bit-identical to a
solo run of that config (tests/test_dse_sweep.py): JAX's while_loop batching
rule keeps finished lanes frozen via select, so early-finishing configs are
unaffected by stragglers.

With the trace-batching frontend (core/batch.py) the same trick applies to
the *workload* axis: whole workloads are padded + stacked into a leading
workload-lane axis, and ``grid_sweep(workloads, cfgs)`` runs the full
benchmarks × configs grid as ONE ``jit(vmap(vmap(run_workload_stacked)))``
program — every (workload, config) lane bit-identical to its solo run
(tests/test_zoo_grid.py; ``python -m repro.launch.zoo --grid 4 4 --check``).

Both sweeps optionally distribute over a 2-D ('cfg', 'sm') device mesh
(core/distribute.py): pass ``mesh=make_mesh(A, B)`` and the lane axis is
sharded over 'cfg' while each lane's SM axis is sharded over 'sm' — the
stacked dynamic-config pytree is placed with an explicit NamedSharding,
and every lane stays bit-identical to its solo run at any mesh shape
(tests/test_mesh_sweep.py).

PR 8 wins the batching bet — the monolithic grid ran at 0.62× a loop of
solo programs because every lane padded to the global max and rode the
longest lane's while_loop.  Three measures, all behind ``RunPlan``
(core/plan.py):

  · **bucketed lane packing** — ``plan.bucket_by='shape'|'cost'`` splits
    the workload lanes into ≤ ``plan.max_buckets`` buckets of similar
    padded shape / predicted cost (core/batch.py:bucket_workloads) and
    compiles one program per bucket, each padded only to ITS max;
  · **ragged layout** — ``plan.layout='ragged'`` concatenates each
    workload's kernels flat with an ``instr_base`` offset table instead of
    NOP-padding to the longest kernel (core/batch.py:concat_workloads);
  · **compile caching** — an in-process AOT executable cache
    (``timed_call(cache_key=...)``) plus jax's persistent compilation
    cache (``plan.cache_dir``) amortize the compile across sweeps and
    processes.

Every bucketed/ragged lane stays bit-identical to its solo run
(tests/test_bucketing.py); results come back in the original lane order
whatever the bucketing.

Usage:
    cfgs = [dataclasses.replace(TINY, l2_lat=v) for v in (16, 32, 64, ...)]
    result = sweep(workload, cfgs)
    result.stats  # list of per-config finalized stat dicts

    grid = grid_sweep([zoo_workload(n) for n in zoo_names()[:4]], cfgs)
    grid.stats[w][c]  # workload-major grid of finalized stat dicts

    plan = RunPlan(mesh=distribute.make_mesh(2, 2), bucket_by="cost")
    grid = grid_sweep(workloads, cfgs, plan=plan)   # same stats, sharded
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core import stats as S
from repro.core import batch
from repro.core.batch import concat_workloads, stack_workloads
from repro.core.engine import run_workload_stacked
from repro.core.parallel import make_sm_runner
from repro.core.plan import RunPlan, resolve_plan
from repro.sim.config import StaticConfig, split_config
from repro.sim.state import init_state
from repro.sim.trace import Workload


def stack_dyn(cfgs):
    """Split each config and stack the typed ``DynConfig`` pytrees along a
    new leading lane axis — scalar leaves become ``(n,)``, the per-class
    ``core.lat``/``core.disp`` tables become ``(n, N_CLASSES)``.

    A lane may be a full ``GPUConfig`` or a pre-split ``(StaticConfig,
    dyn_overrides)`` pair (flat dict or ``DynConfig``) — the raw-table
    route a DSE search loop takes.  All lanes must share the same
    StaticConfig (one shape = one compiled program), and every lane is
    validated at build time, BEFORE any trace: split_config checks the
    override keys, the table lengths, and the machine invariant
    quantum Δ ≤ icnt_lat (config.py:check_dyn) — closing the flat-dict
    bypass of GPUConfig.__post_init__ — and any failure is re-raised
    naming the offending lane."""
    if not cfgs:
        raise ValueError("empty config list")
    splits = []
    for i, c in enumerate(cfgs):
        try:
            if isinstance(c, tuple) and len(c) == 2:
                splits.append(split_config(c[0], c[1]))
            else:
                splits.append(split_config(c))
        except ValueError as e:
            raise ValueError(f"config lane {i}: {e}") from None
    scfg = splits[0][0]
    for i, (s, _) in enumerate(splits):
        if s != scfg:
            raise ValueError(
                f"config {i} has a different static shape than config 0 "
                f"(vmap lanes must share one StaticConfig):\n  {s}\n  {scfg}")
    dyn_batch = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[d for _, d in splits])
    return scfg, dyn_batch


def batched_init(scfg: StaticConfig, *lanes: int) -> dict:
    """One ``init_state`` broadcast to the given leading lane axes —
    (n,) for a sweep, (W, C) for a grid.  Built OUTSIDE the compiled
    program so the runners can DONATE it (``donate_argnums=(0,)``): the
    output state aliases the input buffers and the quantum loop never
    holds two copies of the state in memory at once."""
    st = init_state(scfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, tuple(lanes) + x.shape).copy(), st)


def make_sweep_runner(scfg: StaticConfig, mode: str = "vmap",
                      max_cycles: int = 1 << 20, early_exit: bool = True,
                      donate: bool = True):
    """One compiled program: ``(state_batch, stacked_kernels, dyn_batch)
    -> final state batch``.  ``mode`` picks the SM-phase runner used
    inside every lane.

    The stacked kernel trace is an ARGUMENT (it used to be closed over),
    so one compiled executable serves every workload of the same stacked
    shape — the property the AOT compile cache keys on (``timed_call``).
    The initial state batch (``batched_init``) is an argument too, and
    DONATED by default: the final state aliases its buffers, halving the
    program's peak state footprint (benchmarks/packing.py probes this).
    A donated input is dead after the call — build a fresh state per
    invocation (``sweep`` does)."""
    sm_runner = make_sm_runner(scfg, mode)

    def run_one(state0, stacked, dyn):
        return run_workload_stacked(state0, stacked, scfg, dyn,
                                    sm_runner, max_cycles,
                                    early_exit=early_exit)

    return jax.jit(jax.vmap(run_one, in_axes=(0, None, 0)),
                   donate_argnums=(0,) if donate else ())


def take_lane(batched_state: dict, i: int) -> dict:
    """Slice lane ``i`` out of a batched final state."""
    return jax.tree_util.tree_map(lambda x: x[i], batched_state)


# in-process AOT executable cache: (program key, arg signature) -> compiled.
# Entries are XLA executables, reusable as long as the process lives; the
# cross-process analogue is jax's persistent compilation cache
# (core/plan.py:enable_persistent_cache).
_AOT_CACHE: dict = {}


def _arg_signature(args) -> tuple:
    """Shape/dtype/treedef fingerprint of a call's arguments — what an AOT
    executable is specialized on (beyond the program key)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (str(treedef),
            tuple((tuple(getattr(x, "shape", ())),
                   str(getattr(x, "dtype", type(x).__name__)))
                  for x in leaves))


def aot_cache_key(scfg, plan: RunPlan, what: str) -> tuple:
    """Program identity for the AOT executable cache: everything that
    shapes the traced program besides the argument shapes — the hashable
    StaticConfig, the plan's execution knobs, and which runner (``what``:
    'sweep' | 'grid').  The mesh contributes its shape and device ids."""
    mesh_desc = None
    if plan.mesh is not None:
        mesh_desc = (tuple(plan.mesh.shape.items()),
                     tuple(d.id for d in plan.mesh.devices.flat))
    return (what, scfg, plan.mode, plan.exchange, plan.max_cycles,
            plan.early_exit, mesh_desc)


def clear_aot_cache() -> None:
    _AOT_CACHE.clear()


def timed_call(runner, *args, n_lanes: int = 1, cache_key=None) -> tuple:
    """Run a jitted program with the wall-clock split the run manifests
    record: AOT-lower + compile timed separately from execution, plus
    lanes/sec of the executed program.  ``runner`` is always a
    ``jax.jit``, so a lowering or compile error surfaces here as itself.

    With ``cache_key`` (``aot_cache_key``) the compiled executable is
    memoized on (key, argument shapes/dtypes): a warm call skips lower +
    compile entirely — ``timings['aot_cache']`` reports 'hit'/'miss'.
    Returns (result, timings)."""
    timings = {"n_lanes": n_lanes}
    fn = None
    if cache_key is not None:
        full_key = (cache_key, _arg_signature(args))
        fn = _AOT_CACHE.get(full_key)
        if fn is not None:
            timings["compile_s"] = 0.0
            timings["aot_cache"] = "hit"
    if fn is None:
        t0 = time.perf_counter()
        fn = runner.lower(*args).compile()
        timings["compile_s"] = round(time.perf_counter() - t0, 4)
        if cache_key is not None:
            _AOT_CACHE[full_key] = fn
            timings["aot_cache"] = "miss"
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    timings["execute_s"] = round(time.perf_counter() - t0, 4)
    timings["lanes_per_s"] = round(
        n_lanes / max(timings["execute_s"], 1e-9), 2)
    return out, timings


@dataclass
class SweepResult:
    scfg: StaticConfig
    state: dict                       # batched final state (leading lane axis)
    n: int
    stats: list = field(default_factory=list)   # per-lane finalized dicts
    timings: dict = field(default_factory=dict)  # compile/execute split

    @property
    def cycles(self):
        return [s["cycles"] for s in self.stats]

    def table(self, keys=("cycles", "ipc", "l1_miss", "l2_miss",
                          "dram_req")) -> list:
        return [{k: s[k] for k in keys} for s in self.stats]

    def timelines(self) -> dict:
        """{lane_index_str: (n_used, N_COUNTERS) sample rows} for every
        lane, when the StaticConfig enabled telemetry."""
        from repro.core import telemetry
        if not telemetry.enabled(self.scfg):
            return {}
        return {str(i): telemetry.timeline(take_lane(self.state, i))
                for i in range(self.n)}


def sweep(workload: Workload, cfgs, mode: str = None,
          max_cycles: int = None, mesh=None,
          exchange: str = None, plan: RunPlan = None) -> SweepResult:
    """Run ``workload`` under every config in one compiled, vmapped call.

    Execution knobs come from ``plan=`` (core/plan.py:RunPlan) — mesh
    distribution, trace layout, early-exit, compile caching.  The legacy
    flat kwargs (mode=/max_cycles=/mesh=/exchange=) still work for one
    release via the deprecation shim.  With a mesh, lanes are sharded
    over 'cfg' and each lane's SM axis over 'sm' — same stats, bit-exact,
    at any mesh shape."""
    plan = resolve_plan(plan, where="sweep", mode=mode,
                        max_cycles=max_cycles, mesh=mesh, exchange=exchange)
    plan.activate_caches()
    cfgs = plan.apply_telemetry(cfgs)
    scfg, dyn_batch = stack_dyn(cfgs)
    batch.check_workload_fits(scfg, workload)
    packs = [k.pack() for k in workload.kernels]
    stacked = (batch.concat_kernels(packs) if plan.layout == "ragged"
               else batch.stack_kernels(packs))
    key = aot_cache_key(scfg, plan, "sweep") if plan.aot_cache else None
    state0 = batched_init(scfg, len(cfgs))
    if plan.mesh is not None:
        from repro.core import distribute

        distribute.check_mesh(plan.mesh, scfg, len(cfgs))
        dyn_batch = distribute.place_lanes(dyn_batch, plan.mesh)
        stacked = distribute.place_lanes(
            stacked, plan.mesh, jax.sharding.PartitionSpec())
        state0 = distribute.place_state(state0, plan.mesh,
                                        distribute.CFG_AXIS)
        runner = distribute.make_dist_sweep_runner(
            scfg, plan.mesh, plan.max_cycles, plan.exchange,
            plan.early_exit)
    else:
        runner = make_sweep_runner(scfg, plan.mode, plan.max_cycles,
                                   plan.early_exit)
    bstate, timings = timed_call(runner, state0, stacked, dyn_batch,
                                 n_lanes=len(cfgs), cache_key=key)
    n = len(cfgs)
    stats = [S.finalize(take_lane(bstate, i)) for i in range(n)]
    return SweepResult(scfg=scfg, state=bstate, n=n, stats=stats,
                       timings=timings)


# ---------------------------------------------------------------------------
# grid sweep: benchmarks × configs in one compiled program
# ---------------------------------------------------------------------------

def make_grid_runner(scfg: StaticConfig, mode: str = "vmap",
                     max_cycles: int = 1 << 20, early_exit: bool = True,
                     donate: bool = True):
    """One compiled program for a whole (workload × config) grid:
    ``(state_grid, stacked_workloads, dyn_batch) -> final state`` with
    two leading lane axes (workload-major).  The inner vmap runs every
    config lane of one workload; the outer vmap runs every workload lane
    — all of it one XLA program, one dispatch per quantum for the entire
    grid.  The stacked trace may be padded or ragged (core/batch.py).
    The (W, C)-batched initial state (``batched_init``) is DONATED by
    default — final state aliases it, no second grid-state copy."""
    sm_runner = make_sm_runner(scfg, mode)

    def run_one(state0, stacked, dyn):
        return run_workload_stacked(state0, stacked, scfg, dyn,
                                    sm_runner, max_cycles,
                                    early_exit=early_exit)

    over_cfgs = jax.vmap(run_one, in_axes=(0, None, 0))
    return jax.jit(jax.vmap(over_cfgs, in_axes=(0, 0, None)),
                   donate_argnums=(0,) if donate else ())


def take_grid_lane(batched_state: dict, w: int, c: int) -> dict:
    """Slice lane (workload ``w``, config ``c``) out of a grid state."""
    return jax.tree_util.tree_map(lambda x: x[w, c], batched_state)


@dataclass
class GridResult:
    scfg: StaticConfig
    state: dict          # final state, leading (workload, config) lane axes
    names: list          # workload names, grid row order
    n_workloads: int
    n_cfgs: int
    stats: list = field(default_factory=list)   # stats[w][c] finalized dict
    timings: dict = field(default_factory=dict)  # compile/execute split
    # bucketed runs: [(workload_indices, bucket_state), ...] — each bucket
    # was its own compiled program; ``state`` is then the first bucket's
    # only if the grid was monolithic (single bucket), else None
    buckets: list = None

    def lane_state(self, w: int, c: int) -> dict:
        """Final state of lane (workload ``w``, config ``c``), whichever
        bucket it ran in."""
        if self.buckets is not None:
            for idxs, bstate in self.buckets:
                if w in idxs:
                    return take_grid_lane(bstate, idxs.index(w), c)
            raise KeyError(f"workload index {w} in no bucket")
        return take_grid_lane(self.state, w, c)

    def table(self, keys=("cycles", "ipc", "l1_miss", "l2_miss",
                          "dram_req")) -> list:
        return [{"workload": self.names[w], "cfg": c,
                 **{k: self.stats[w][c][k] for k in keys}}
                for w in range(self.n_workloads)
                for c in range(self.n_cfgs)]

    def timelines(self) -> dict:
        """{"<workload>/<cfg>": (n_used, N_COUNTERS) sample rows} per grid
        lane, when the StaticConfig enabled telemetry."""
        from repro.core import telemetry
        if not telemetry.enabled(self.scfg):
            return {}
        return {f"{self.names[w]}/{c}": telemetry.timeline(
                    self.lane_state(w, c))
                for w in range(self.n_workloads)
                for c in range(self.n_cfgs)}


def _run_grid_bucket(workloads, scfg, dyn_batch, plan: RunPlan,
                     n_cfgs: int):
    """One compiled grid program over a bucket of workloads: stack (or
    ragged-concat) the bucket's workloads — padded only to the BUCKET's
    max shape — and run all its (workload × config) lanes."""
    stacked = (concat_workloads(workloads) if plan.layout == "ragged"
               else stack_workloads(workloads))
    key = aot_cache_key(scfg, plan, "grid") if plan.aot_cache else None
    state0 = batched_init(scfg, len(workloads), n_cfgs)
    if plan.mesh is not None:
        from repro.core import distribute

        stacked = distribute.place_lanes(
            stacked, plan.mesh, jax.sharding.PartitionSpec())
        state0 = distribute.place_state(state0, plan.mesh, None,
                                        distribute.CFG_AXIS)
        runner = distribute.make_dist_grid_runner(
            scfg, plan.mesh, plan.max_cycles, plan.exchange,
            plan.early_exit)
    else:
        runner = make_grid_runner(scfg, plan.mode, plan.max_cycles,
                                  plan.early_exit)
    return timed_call(runner, state0, stacked, dyn_batch,
                      n_lanes=len(workloads) * n_cfgs, cache_key=key)


def bucket_groups(workloads, plan: RunPlan, scfg: StaticConfig) -> list:
    """The one bucket-forming policy ``grid_sweep`` and ``pair_sweep``
    share: partition the workload-lane indices per ``plan.bucket_by`` /
    ``plan.max_buckets`` (core/batch.py:bucket_workloads), seeding 'cost'
    keys from measured run-manifest hints refined by the analytic model
    when the bucket count is chosen automatically."""
    hints = None
    max_buckets = plan.max_buckets
    if plan.bucket_by == "cost":
        hints = batch.cost_hints_from_manifests()
        if max_buckets is None:
            # cost-model-driven bucket counts: lanes without a measured
            # manifest hint get an analytically-predicted cost key, and
            # bucket_workloads(max_buckets=None) minimizes the predicted
            # total padded cost over the candidate counts
            from repro.core import analytic
            hints = dict({w.name: analytic.predicted_workload_cost(w, scfg)
                          for w in workloads}, **hints)
    elif max_buckets is None:
        max_buckets = 4            # the classic ceiling for non-cost modes
    return batch.bucket_workloads(workloads, plan.bucket_by,
                                  max_buckets, hints)


def grid_sweep(workloads, cfgs, mode: str = None,
               max_cycles: int = None, mesh=None,
               exchange: str = None, plan: RunPlan = None) -> GridResult:
    """Simulate every workload under every config — W×C lanes, one
    compiled call per BUCKET.  Workloads are padded to a shared (kernel
    count, instruction count) with inert kernels/NOP slots (or
    ragged-concatenated, ``plan.layout``), so each lane is bit-identical
    to a solo ``simulate()`` of that (workload, config) pair.

    ``plan.bucket_by`` ('shape'/'cost') groups the workload lanes into
    ≤ ``plan.max_buckets`` buckets of similar padded shape / predicted
    cost (core/batch.py:bucket_workloads) and compiles one program per
    bucket — short lanes stop riding the longest lane's while_loop, which
    is what makes the batched grid beat a loop of solo programs
    (benchmarks/packing.py).  Stats come back in the original lane order.

    With a mesh (2-D ('cfg', 'sm'), core/distribute.py) config lanes are
    sharded over 'cfg', each lane's SM axis over 'sm'; the workload axis
    is replicated.  Stats are bit-exact at any mesh shape."""
    plan = resolve_plan(plan, where="grid_sweep", mode=mode,
                        max_cycles=max_cycles, mesh=mesh, exchange=exchange)
    plan.activate_caches()
    cfgs = plan.apply_telemetry(cfgs)
    scfg, dyn_batch = stack_dyn(cfgs)
    for w in workloads:
        batch.check_workload_fits(scfg, w)
    if plan.mesh is not None:
        from repro.core import distribute

        distribute.check_mesh(plan.mesh, scfg, len(cfgs))
        dyn_batch = distribute.place_lanes(dyn_batch, plan.mesh)

    nw, nc = len(workloads), len(cfgs)
    groups = bucket_groups(workloads, plan, scfg)

    stats = [[None] * nc for _ in range(nw)]
    bucket_states = []
    timings = {"n_lanes": nw * nc, "n_buckets": len(groups),
               "compile_s": 0.0, "execute_s": 0.0}
    for idxs in groups:
        bstate, tm = _run_grid_bucket([workloads[i] for i in idxs], scfg,
                                      dyn_batch, plan, nc)
        bucket_states.append((list(idxs), bstate))
        for pos, w in enumerate(idxs):
            for c in range(nc):
                stats[w][c] = S.finalize(take_grid_lane(bstate, pos, c))
        timings["compile_s"] = round(
            timings["compile_s"] + tm["compile_s"], 4)
        timings["execute_s"] = round(
            timings["execute_s"] + tm["execute_s"], 4)
        if "aot_cache" in tm:
            timings["aot_cache"] = tm["aot_cache"] if \
                timings.get("aot_cache") in (None, tm["aot_cache"]) \
                else "mixed"
    timings["lanes_per_s"] = round(
        nw * nc / max(timings["execute_s"], 1e-9), 2)
    single = bucket_states[0][1] if len(groups) == 1 else None
    return GridResult(scfg=scfg, state=single,
                      names=[w.name for w in workloads],
                      n_workloads=nw, n_cfgs=nc, stats=stats,
                      timings=timings, buckets=bucket_states)


# ---------------------------------------------------------------------------
# pair sweep: heterogeneous (workload, config) lanes — the serving batcher
# ---------------------------------------------------------------------------

def make_pair_runner(scfg: StaticConfig, mode: str = "vmap",
                     max_cycles: int = 1 << 20, early_exit: bool = True,
                     donate: bool = True):
    """One compiled program over a batch of *pair* lanes: every lane
    carries its OWN workload and its OWN dynamic config —
    ``(state_batch, stacked_workloads, dyn_batch) -> final state batch``
    with all three arguments vmapped along the lane axis
    (``in_axes=(0, 0, 0)``), unlike the grid runner's workload × config
    cross product.  This is the shape a simulation server's continuous
    batcher needs (core/service.py): N unrelated submissions — different
    benchmarks, different timing points — advance together as N lanes of
    one XLA program.  The (n,)-batched initial state is DONATED."""
    sm_runner = make_sm_runner(scfg, mode)

    def run_one(state0, stacked, dyn):
        return run_workload_stacked(state0, stacked, scfg, dyn,
                                    sm_runner, max_cycles,
                                    early_exit=early_exit)

    return jax.jit(jax.vmap(run_one, in_axes=(0, 0, 0)),
                   donate_argnums=(0,) if donate else ())


@dataclass
class PairResult:
    """Result of a ``pair_sweep``: per-lane finalized stats in submission
    order, whatever the bucketing, plus the per-bucket final states."""
    scfg: StaticConfig
    n: int
    stats: list = field(default_factory=list)    # per-lane finalized dicts
    timings: dict = field(default_factory=dict)  # compile/execute split
    # [(lane_indices, bucket_state), ...] — lane i's state sits at
    # position lane_indices.index(i) of its bucket (duplicate fill lanes
    # past len(lane_indices) are discarded)
    buckets: list = field(default_factory=list)

    def lane_state(self, i: int) -> dict:
        for idxs, bstate in self.buckets:
            if i in idxs:
                return take_lane(bstate, idxs.index(i))
        raise KeyError(f"lane index {i} in no bucket")


def _pad_fill(idxs: list, lane_quantum: int | None) -> list:
    """Round a bucket's lane list up to a multiple of ``lane_quantum`` by
    repeating its own lanes cyclically — padded slots carry LIVE work
    (a duplicate of a real lane is bit-identical and independent under
    vmap) instead of inert NOPs, and the rounded lane counts keep the
    AOT executable cache hot across batches of drifting size."""
    if not lane_quantum or lane_quantum <= 1:
        return list(idxs)
    n = len(idxs)
    padded = ((n + lane_quantum - 1) // lane_quantum) * lane_quantum
    return [idxs[j % n] for j in range(padded)]


def pair_sweep(pairs, plan: RunPlan = None,
               lane_quantum: int | None = None) -> PairResult:
    """Run a heterogeneous batch of (workload, config) PAIR lanes — lane
    ``i`` simulates ``pairs[i] = (workload_i, cfg_i)`` — in one compiled
    vmapped program per bucket.  This is the execution primitive behind
    the simulation server (core/service.py): unlike ``grid_sweep``'s
    cross product, every lane is an independent submission, so unrelated
    jobs co-batch whenever their workloads share a padded footprint
    bucket (``plan.bucket_by``, core/batch.py:bucket_workloads).

    Every lane is bit-identical to a solo ``simulate(workload, cfg)`` of
    its pair regardless of which strangers it was batched with, the
    arrival order, or the batch boundaries (tests/test_service.py) — the
    vmap/padding machinery is exactly the grid's, which
    tests/test_zoo_grid.py pins against solo runs.

    ``lane_quantum`` rounds each bucket's lane count up to a multiple by
    repeating live lanes (``_pad_fill``); duplicate results are dropped.
    All configs must share one StaticConfig; the mesh path is not wired
    for pair lanes (use grid_sweep for mesh runs)."""
    plan = resolve_plan(plan, where="pair_sweep")
    if plan.mesh is not None:
        raise ValueError("pair_sweep does not support mesh distribution; "
                         "use grid_sweep for mesh runs")
    if not pairs:
        raise ValueError("empty pair list")
    plan.activate_caches()
    workloads = [w for w, _ in pairs]
    cfgs = plan.apply_telemetry([c for _, c in pairs])
    scfg, _ = stack_dyn(cfgs)          # validates the shared static shape
    for w in workloads:
        batch.check_workload_fits(scfg, w)
    groups = bucket_groups(workloads, plan, scfg)

    n = len(pairs)
    stats = [None] * n
    bucket_states = []
    timings = {"n_lanes": n, "n_buckets": len(groups),
               "compile_s": 0.0, "execute_s": 0.0}
    key = aot_cache_key(scfg, plan, "pair") if plan.aot_cache else None
    for idxs in groups:
        fill = _pad_fill(idxs, lane_quantum)
        ws = [workloads[i] for i in fill]
        stacked = (concat_workloads(ws) if plan.layout == "ragged"
                   else stack_workloads(ws))
        _, dyn_b = stack_dyn([cfgs[i] for i in fill])
        state0 = batched_init(scfg, len(fill))
        runner = make_pair_runner(scfg, plan.mode, plan.max_cycles,
                                  plan.early_exit)
        bstate, tm = timed_call(runner, state0, stacked, dyn_b,
                                n_lanes=len(idxs), cache_key=key)
        bucket_states.append((list(idxs), bstate))
        for pos, i in enumerate(idxs):      # duplicates past len(idxs) drop
            stats[i] = S.finalize(take_lane(bstate, pos))
        timings["compile_s"] = round(
            timings["compile_s"] + tm["compile_s"], 4)
        timings["execute_s"] = round(
            timings["execute_s"] + tm["execute_s"], 4)
        if "aot_cache" in tm:
            timings["aot_cache"] = tm["aot_cache"] if \
                timings.get("aot_cache") in (None, tm["aot_cache"]) \
                else "mixed"
    timings["lanes_per_s"] = round(n / max(timings["execute_s"], 1e-9), 2)
    return PairResult(scfg=scfg, n=n, stats=stats, timings=timings,
                      buckets=bucket_states)
