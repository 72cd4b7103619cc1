"""RunPlan — the one typed home for every execution knob of a run.

PR 1–6 grew ``sweep(workload, cfgs, mode=, max_cycles=, mesh=,
exchange=, ...)`` one keyword at a time; the batching work (bucketed lane
packing, ragged layouts, early-exit, compile caching) would have added
five more.  ``RunPlan`` collapses that sprawl: a frozen dataclass that
``sweep`` / ``grid_sweep`` / ``simulate`` (core/sweep.py, core/engine.py),
both launchers (via launch/cli.py) and the benchmarks thread through
unchanged — one place to add a knob, one place to validate it.

Fields by concern:

  execution   ``mode`` (seq/vmap), ``mesh`` + ``exchange`` (2-D
              ('cfg','sm') distribution, core/distribute.py),
              ``max_cycles`` (per-kernel quantum-loop horizon),
              ``early_exit`` (entry-converged lanes charge zero quanta —
              core/engine.py).
  packing     ``bucket_by`` ('none' | 'shape' | 'cost'): split the
              workload lanes of a grid into ≤ ``max_buckets`` buckets of
              similar padded shape / predicted cost and compile one
              program per bucket, so short lanes stop riding the longest
              lane's while_loop (core/batch.py:bucket_workloads).
              ``layout`` ('padded' | 'ragged'): per-bucket trace layout —
              'ragged' concatenates kernels with an ``instr_base`` offset
              table (the cu_seqlens unpadded-varlen idiom) instead of
              NOP-padding every kernel to the longest one.
  telemetry   ``telemetry_samples`` / ``telemetry_every`` — applied to
              the lanes' StaticConfig (all-lanes-or-none) by
              ``apply_telemetry``.
  caching     ``cache_dir`` — persistent XLA compilation cache directory
              (amortizes compiles across *processes*);  ``aot_cache`` —
              in-process memo of AOT-compiled executables keyed on
              (StaticConfig, input shapes, plan knobs), so re-sweeping a
              known bucket shape skips lower+compile entirely
              (core/sweep.py:timed_call).

Legacy keyword compatibility: ``resolve_plan`` lets the old flat kwargs
(`mode=`, `max_cycles=`, `mesh=`, `exchange=`) keep working for one
release — they build a RunPlan and warn once (DeprecationWarning).
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from dataclasses import dataclass

MODES = ("seq", "vmap")
EXCHANGES = ("window", "cycle")
BUCKET_POLICIES = ("none", "shape", "cost")
LAYOUTS = ("padded", "ragged")


@dataclass(frozen=True)
class RunPlan:
    """Every execution knob of a ``sweep``/``grid_sweep``/``simulate``
    call, validated once at construction.  See the module docstring for
    the field-by-field story."""
    # execution
    mode: str = "vmap"
    mesh: object = None          # jax.sharding.Mesh with ('cfg','sm') axes
    exchange: str = "window"
    max_cycles: int = 1 << 20
    early_exit: bool = True
    # packing.  max_buckets=None with bucket_by='cost' picks the bucket
    # count automatically by minimizing the analytically-predicted total
    # padded cost (core/batch.py:choose_bucket_count); with other
    # policies None falls back to the classic ceiling of 4.
    bucket_by: str = "none"
    max_buckets: int | None = 4
    layout: str = "padded"
    # telemetry (sized into the lanes' StaticConfig — all lanes or none)
    telemetry_samples: int = 0
    telemetry_every: int = 1
    # compile caching
    cache_dir: str | None = None
    aot_cache: bool = True
    # analytic-prune search (core/search.py): proposer seed, rounds of
    # propose→score→verify, and how many predicted-best candidates each
    # round's ONE cycle-accurate sweep verifies
    search_seed: int = 0
    search_rounds: int = 3
    search_topk: int = 8

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"RunPlan.mode must be one of {MODES}, got {self.mode!r} "
                "(SM-axis 'shard' execution is reached via mesh=, not "
                "mode=)")
        if self.exchange not in EXCHANGES:
            raise ValueError(
                f"RunPlan.exchange must be one of {EXCHANGES}, got "
                f"{self.exchange!r}")
        if self.bucket_by not in BUCKET_POLICIES:
            raise ValueError(
                f"RunPlan.bucket_by must be one of {BUCKET_POLICIES}, got "
                f"{self.bucket_by!r}")
        if self.layout not in LAYOUTS:
            raise ValueError(
                f"RunPlan.layout must be one of {LAYOUTS}, got "
                f"{self.layout!r}")
        if self.max_cycles <= 0:
            raise ValueError(
                f"RunPlan.max_cycles must be positive, got "
                f"{self.max_cycles}")
        if self.max_buckets is not None and self.max_buckets < 1:
            raise ValueError(
                f"RunPlan.max_buckets must be ≥ 1 (or None for the "
                f"cost-model-driven automatic count), got "
                f"{self.max_buckets}")
        if self.search_seed < 0:
            raise ValueError(
                f"RunPlan.search_seed must be ≥ 0, got {self.search_seed}")
        if self.search_rounds < 1:
            raise ValueError(
                f"RunPlan.search_rounds must be ≥ 1, got "
                f"{self.search_rounds}")
        if self.search_topk < 1:
            raise ValueError(
                f"RunPlan.search_topk must be ≥ 1, got {self.search_topk}")
        if self.telemetry_samples < 0:
            raise ValueError(
                f"RunPlan.telemetry_samples must be ≥ 0, got "
                f"{self.telemetry_samples}")
        if self.telemetry_every < 1:
            raise ValueError(
                f"RunPlan.telemetry_every must be ≥ 1, got "
                f"{self.telemetry_every}")
        if self.mesh is not None:
            if self.mode != "vmap":
                raise ValueError(
                    f"RunPlan.mode={self.mode!r} conflicts with mesh=: the "
                    "distributed path has its own in-lane execution "
                    "(sharded SM axis); use mode='vmap' (the default) or "
                    "drop mesh=")
            names = tuple(getattr(self.mesh, "axis_names", ()))
            if "cfg" not in names or "sm" not in names:
                raise ValueError(
                    "RunPlan.mesh must be a 2-D ('cfg','sm') mesh "
                    f"(core/distribute.py:make_mesh), got axes {names}")

    # -- telemetry ----------------------------------------------------------

    def apply_telemetry(self, cfgs):
        """Size the counter-timeline buffer into every lane's static half
        (no-op when ``telemetry_samples == 0``).  Lanes may be full
        GPUConfig / StaticConfig objects or pre-split ``(StaticConfig,
        overrides)`` pairs — all of them must share one StaticConfig, so
        telemetry is all-lanes-or-none."""
        if self.telemetry_samples <= 0:
            return cfgs
        kw = dict(telemetry_samples=self.telemetry_samples,
                  telemetry_every=self.telemetry_every)

        def one(c):
            if isinstance(c, tuple) and len(c) == 2:
                return (dataclasses.replace(c[0], **kw), c[1])
            return dataclasses.replace(c, **kw)

        if isinstance(cfgs, (list, tuple)):
            return [one(c) for c in cfgs]
        return one(cfgs)

    # -- cache wiring -------------------------------------------------------

    def activate_caches(self) -> None:
        """Wire the persistent XLA compilation cache when ``cache_dir`` is
        set (idempotent; safe to call per sweep)."""
        if self.cache_dir:
            enable_persistent_cache(self.cache_dir)

    def describe(self) -> dict:
        """JSON-safe summary for run manifests / bench artifacts."""
        mesh = None
        if self.mesh is not None:
            mesh = [int(self.mesh.shape["cfg"]), int(self.mesh.shape["sm"])]
        return {
            "mode": self.mode, "mesh": mesh, "exchange": self.exchange,
            "max_cycles": self.max_cycles, "early_exit": self.early_exit,
            "bucket_by": self.bucket_by, "max_buckets": self.max_buckets,
            "layout": self.layout,
            "telemetry_samples": self.telemetry_samples,
            "telemetry_every": self.telemetry_every,
            "cache_dir": self.cache_dir, "aot_cache": self.aot_cache,
            "search_seed": self.search_seed,
            "search_rounds": self.search_rounds,
            "search_topk": self.search_topk,
        }


# ---------------------------------------------------------------------------
# legacy flat-kwarg shim (one release: warn once, then drop)
# ---------------------------------------------------------------------------

_warned_legacy = False


def _warn_legacy_once(where: str) -> None:
    global _warned_legacy
    if not _warned_legacy:
        _warned_legacy = True
        warnings.warn(
            f"{where} received legacy flat keyword(s) (mode=/max_cycles=/"
            "mesh=/exchange=); pass plan=RunPlan(...) instead — the flat "
            "kwargs build a RunPlan for you now and will be removed next "
            "release.", DeprecationWarning, stacklevel=4)


def resolve_plan(plan, *, where: str = "sweep", mode=None, max_cycles=None,
                 mesh=None, exchange=None) -> RunPlan:
    """The one entry point ``sweep``/``grid_sweep``/``simulate`` funnel
    their arguments through.

    ``plan`` given → legacy kwargs must be absent (mixing the two would
    leave a knob with two homes).  ``plan`` absent → any legacy kwargs
    build one (warn once); a bare string in the plan slot is tolerated as
    the old positional ``mode``."""
    if isinstance(plan, str):          # old positional: sweep(w, cfgs, "seq")
        if mode is not None:
            raise ValueError(f"{where}: mode given twice ({plan!r} and "
                             f"{mode!r})")
        plan, mode = None, plan
    legacy = {k: v for k, v in (("mode", mode), ("max_cycles", max_cycles),
                                ("mesh", mesh), ("exchange", exchange))
              if v is not None}
    if plan is not None:
        if legacy:
            raise ValueError(
                f"{where}: pass either plan= or the legacy flat kwargs "
                f"({sorted(legacy)}), not both — every knob lives on the "
                "RunPlan now")
        if not isinstance(plan, RunPlan):
            raise TypeError(
                f"{where}: plan must be a RunPlan, got {type(plan).__name__}")
        return plan
    if legacy:
        _warn_legacy_once(where)
    return RunPlan(**legacy)


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

_persistent_cache_dir = None

# the one fixed cache path used when JAX_COMPILATION_CACHE_DIR is unset:
# inside the checkout (gitignored), never temp-, pid- or time-derived, so
# every process of this checkout finds what an earlier one compiled
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_persistent_cache(cache_dir: str | None = None) -> str:
    """Point jax's persistent compilation cache at a directory so compiled
    programs survive the process — the ~17 s mesh-grid compile is paid
    once per (StaticConfig, bucket shape), not once per run.

    Where the directory comes from, first match wins:
      1. ``JAX_COMPILATION_CACHE_DIR`` — jax reads it itself, and no other
         directory is set in code (``cache_dir`` is ignored);
      2. ``cache_dir`` (``RunPlan.cache_dir``, ``--cache-dir``);
      3. ``DEFAULT_CACHE_DIR``.
    Entry points (``launch/*.py`` mains, ``chip_smoke.py``,
    ``benchmarks/run.py``) call this with no argument or their
    ``--cache-dir``; library calls wire only an explicit ``cache_dir``.

    Idempotent; re-wiring to a *different* directory raises (jax reads the
    config at compile time, silently splitting the cache would be worse).
    Returns the active directory."""
    global _persistent_cache_dir
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = os.path.abspath(env_dir or cache_dir or DEFAULT_CACHE_DIR)
    if _persistent_cache_dir is not None:
        if cache_dir != _persistent_cache_dir:
            raise ValueError(
                f"persistent compile cache already wired to "
                f"{_persistent_cache_dir}; refusing to re-wire to "
                f"{cache_dir} mid-process")
        return _persistent_cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program, however small/fast — simulator programs are
    # worth re-using even when XLA thinks they compiled "quickly"
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _persistent_cache_dir = cache_dir
    return cache_dir
