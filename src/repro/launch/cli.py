"""Shared launcher CLI surface — one home for the RunPlan flags.

launch/dse.py and launch/zoo.py used to carry duplicated argparse blocks
(--mesh/--telemetry/--telemetry-every/--profile/--no-manifest/
--sample-*) that had already drifted once; with the PR-8 packing knobs
(--bucket-by/--max-buckets/--layout/--cache-dir/--no-early-exit) joining
them, the duplication would have doubled.  ``add_plan_args`` installs
the shared flags on a parser and ``plan_from_args`` turns the parsed
namespace into the typed ``RunPlan`` (core/plan.py) that
``sweep``/``grid_sweep``/``simulate`` accept — so a launcher adds ONE
call at each end and every execution knob flows through the same
validated object.

``add_sample_args`` covers the per-class timing-table sweep triples
(--sample-lat/--sample-disp), shared by both launchers but not part of
the RunPlan (they shape the CONFIG GRID, not the execution).
"""
from __future__ import annotations

import argparse
import contextlib

from repro.core.plan import BUCKET_POLICIES, LAYOUTS, RunPlan


def add_plan_args(ap: argparse.ArgumentParser) -> None:
    """Install the shared execution/packing/observability flags.  Read
    them back with ``plan_from_args``."""
    # -- execution / distribution ------------------------------------------
    ap.add_argument("--mesh", nargs=2, type=int, metavar=("A", "B"),
                    help="distribute over a 2-D ('cfg','sm') device mesh — "
                         "A cfg-devices × B sm-devices (needs A*B devices; "
                         "on CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count before jax initializes)")
    ap.add_argument("--max-cycles", type=int, default=1 << 15,
                    help="per-kernel quantum-loop horizon (timeout guard)")
    ap.add_argument("--no-early-exit", action="store_true",
                    help="disable the entry-convergence early exit "
                         "(core/engine.py) — debugging knob; results are "
                         "bit-identical either way")
    # -- bucketed lane packing ---------------------------------------------
    ap.add_argument("--bucket-by", choices=BUCKET_POLICIES, default="none",
                    help="group grid workload lanes into buckets of "
                         "similar padded shape / predicted cost and "
                         "compile one program per bucket "
                         "(core/batch.py:bucket_workloads)")
    ap.add_argument("--max-buckets", type=int, default=None,
                    help="bucket count ceiling for --bucket-by; unset with "
                         "--bucket-by cost picks the count that minimizes "
                         "the predicted total padded cost "
                         "(core/batch.py:choose_bucket_count), unset "
                         "otherwise keeps the classic ceiling of 4")
    ap.add_argument("--layout", choices=LAYOUTS, default="padded",
                    help="kernel-trace layout: 'ragged' concatenates "
                         "kernels with an instr_base offset table instead "
                         "of NOP-padding to the longest kernel")
    # -- compile caching ----------------------------------------------------
    ap.add_argument("--cache-dir", default="", metavar="DIR",
                    help="persistent XLA compilation cache directory — "
                         "compiled programs survive the process; "
                         "JAX_COMPILATION_CACHE_DIR, when set, wins, and "
                         "the default is <repo>/.jax_cache "
                         "(core/plan.py:enable_persistent_cache)")
    ap.add_argument("--no-aot-cache", action="store_true",
                    help="disable the in-process AOT executable cache "
                         "(core/sweep.py:timed_call)")
    # -- observability ------------------------------------------------------
    ap.add_argument("--telemetry", type=int, default=0, metavar="S",
                    help="sample the per-SM counter timeline into S "
                         "preallocated rows per lane (core/telemetry.py); "
                         "0 = off (compiled program unchanged)")
    ap.add_argument("--telemetry-every", type=int, default=1, metavar="N",
                    help="sampling cadence in quanta (default 1)")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="capture a jax.profiler (XLA-level) trace of the "
                         "run into DIR, alongside the manifest; every op of "
                         "the quantum loop carries its phase scope "
                         "(sim.mem_phase, sim.cta_issue, sim.sm_phase, "
                         "sim.loop_control; core/engine.py:PHASES) in its "
                         "op_name")
    ap.add_argument("--no-manifest", action="store_true",
                    help="skip writing the run manifest JSON under "
                         "experiments/runs/")


def add_sample_args(ap: argparse.ArgumentParser, when: str) -> None:
    """The per-class timing-table sweep triples (repeatable), shared by
    both launchers; ``when`` names the flag they depend on in help."""
    ap.add_argument("--sample-lat", nargs=3, action="append", default=[],
                    metavar=("CLASS", "LO", "HI"),
                    help=f"with {when}: config lanes step the per-class "
                         "result latency of CLASS "
                         "(fp32/int32/sfu/tensor/ldg/stg/bar) from LO to "
                         "HI; repeatable")
    ap.add_argument("--sample-disp", nargs=3, action="append", default=[],
                    metavar=("CLASS", "LO", "HI"),
                    help=f"with {when}: config lanes step the per-class "
                         "dispatch interval of CLASS from LO to HI; "
                         "repeatable")
    ap.add_argument("--sample-seed", type=int, default=None, metavar="SEED",
                    help="draw the --sample-* lanes uniformly at random "
                         "from [LO, HI] with this seed instead of the "
                         "deterministic LO..HI linear steps (PCG64; same "
                         "seed, same lanes)")


def add_search_args(ap: argparse.ArgumentParser) -> None:
    """The analytic-prune search knobs (core/search.py), dse-only."""
    ap.add_argument("--search", action="store_true",
                    help="search the config space instead of sweeping a "
                         "fixed grid: propose candidates, score them ALL "
                         "with the analytical surrogate (core/analytic.py),"
                         " cycle-accurately verify only the predicted "
                         "top-k per round (core/search.py)")
    ap.add_argument("--search-rounds", type=int, default=3,
                    help="propose→score→verify rounds (default 3)")
    ap.add_argument("--search-topk", type=int, default=8,
                    help="candidates verified per round in ONE sweep() "
                         "call (default 8)")
    ap.add_argument("--search-seed", type=int, default=0,
                    help="proposer seed — the full candidate sequence and "
                         "top-k are bit-reproducible per seed")
    ap.add_argument("--search-cands", type=int, default=256,
                    help="candidates proposed and analytically scored per "
                         "round (default 256)")
    ap.add_argument("--search-spread", type=float, default=2.0,
                    help="search box half-width: each base config entry "
                         "spans [v/spread, v*spread] (default 2.0); "
                         "--sample-* triples override per-class table "
                         "bounds")


def plan_from_args(args: argparse.Namespace) -> RunPlan:
    """The parsed shared flags as a validated RunPlan.  Builds the mesh
    here (--mesh A B), so launchers never touch jax devices directly."""
    mesh = None
    if getattr(args, "mesh", None):
        from repro.core.distribute import make_mesh
        mesh = make_mesh(*args.mesh)
    return RunPlan(
        mesh=mesh,
        max_cycles=args.max_cycles,
        early_exit=not args.no_early_exit,
        bucket_by=args.bucket_by,
        max_buckets=args.max_buckets,
        layout=args.layout,
        cache_dir=args.cache_dir or None,
        aot_cache=not args.no_aot_cache,
        telemetry_samples=args.telemetry,
        telemetry_every=args.telemetry_every,
        # search knobs exist only on parsers that called add_search_args
        search_seed=getattr(args, "search_seed", 0),
        search_rounds=getattr(args, "search_rounds", 3),
        search_topk=getattr(args, "search_topk", 8),
    )


def add_service_args(ap: argparse.ArgumentParser) -> None:
    """The sim-server knobs (launch/serve.py → core/service.py): base
    hardware config and the batch-former's flush rule."""
    ap.add_argument("--base", choices=("tiny", "3080ti"), default="tiny",
                    help="base GPU config the server compiles for; job "
                         "overrides may only touch dynamic knobs "
                         "(sim/config.py:DYNAMIC_FIELDS + scheduler + "
                         "per-class tables)")
    ap.add_argument("--batch-lanes", type=int, default=8,
                    help="flush the queue once this many lanes are "
                         "waiting (the batch-size half of the flush rule)")
    ap.add_argument("--max-wait-ms", type=float, default=50.0,
                    help="flush when the oldest pending job has waited "
                         "this long (the deadline half of the flush rule)")
    ap.add_argument("--lane-quantum", type=int, default=None, metavar="Q",
                    help="round each bucket's lane count up to a multiple "
                         "of Q by repeating live lanes — padded slots "
                         "carry real requests and AOT signatures stay "
                         "stable as batch sizes drift")
    ap.add_argument("--manifests", action="store_true",
                    help="write a per-job run manifest (queue/compile/"
                         "execute latency split) under experiments/runs/")


def base_config(name: str):
    from repro.sim.config import RTX3080TI, TINY
    return {"tiny": TINY, "3080ti": RTX3080TI}[name]


def service_from_args(args: argparse.Namespace, plan=None):
    """A configured (threaded) SimService from the parsed service+plan
    flags."""
    from repro.core.service import SimService
    return SimService(
        base=base_config(args.base),
        plan=plan,
        batch_lanes=args.batch_lanes,
        max_wait_s=args.max_wait_ms / 1000.0,
        lane_quantum=args.lane_quantum,
        manifests=args.manifests,
    )


def profile_ctx(args):
    """jax.profiler trace capture context for --profile DIR (nullcontext
    when off)."""
    if not getattr(args, "profile", ""):
        return contextlib.nullcontext()
    import jax
    return jax.profiler.trace(args.profile)
