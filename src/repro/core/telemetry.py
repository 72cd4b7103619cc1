"""Cycle-resolved counter timelines + run-manifest telemetry.

Two halves, one module:

**In-trace timelines** — when ``StaticConfig.telemetry_samples > 0`` the
state pytree (sim/state.py:init_state) grows a ``telem`` part: a
preallocated ``(telemetry_samples, N_COUNTERS)`` int32 ring-free buffer, a
write index, and a cumulative *lockstep-waste* accumulator.  Every
``telemetry_every``-th quantum the engine snapshots the cumulative per-SM
counters (summed over SMs), the global memory-system counters, the
instantaneous live-warp count and the waste accumulator into the next
buffer row (``sample``); the end of every kernel forces a snapshot, so the
LAST written row always equals the run's final cumulative counters —
the invariant tests/test_telemetry.py locks against ``stats.finalize``.
Lockstep waste counts, per quantum, Δ cycles for every SM that sits fully
converged (no live warps, no in-flight memory requests) while the kernel
as a whole is still running — the cycles the lockstep ``while_loop`` burns
riding the longest SM/lane, the suspected cause of the batched-grid
regression in ROADMAP's top open item.

The buffer lives INSIDE the traced program, so timelines ride every
execution path unchanged: vmapped config lanes (core/sweep.py) carry a
leading lane axis, grid sweeps two, and under the 2-D ('cfg', 'sm') mesh
(core/distribute.py) the counter reductions ``psum`` over the 'sm' axis so
the replicated buffer holds full-machine totals.  With telemetry disabled
(the default) the state pytree and the compiled program are bit-for-bit
unchanged — the determinism golden needs no regeneration.

**Compile counters** — one process-wide counter set of compile work,
filled from JAX's own monitoring events through listeners registered
once, when this module is imported (the engine imports it before it
builds a program, so the counters see every compile of a simulation):
how many times a function was traced to a jaxpr, lowered to an MLIR
module, and compiled by the backend (or loaded from the persistent
cache), the seconds of each stage, and the persistent cache's hits,
misses and retrieval seconds.  ``compile_counters()`` is a snapshot and
``compile_delta(a, b)`` the work between two snapshots.

**Run manifests** — every launcher/bench run can write a structured JSON
manifest under ``experiments/runs/``: git sha, StaticConfig hash, host
context (hostname, device kind/count, XLA_FLAGS), mesh shape, the
compile-vs-execute wall-clock split and lanes/sec of the compiled
program, final per-lane stats, and the sampled timelines.
``launch/report.py`` renders/diffs them.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from dataclasses import asdict

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# counter layout
# ---------------------------------------------------------------------------

# cumulative per-SM counters (sim/state.py "stats_sm"), summed over SMs at
# sample time — each matches the identically-named stats.finalize total
CUM_SM = ("issued", "issued_mem", "l1_hit", "l1_miss", "cycles_issue",
          "stall", "warp_cycles")
# cumulative global counters (serial-region "stats")
CUM_GLOBAL = ("l2_hit", "l2_miss", "dram_req", "dram_row_hit",
              "ctas_launched")
# gauges: instantaneous / telemetry-only values
GAUGES = ("active_warps", "lockstep_waste")
COUNTERS = ("cycle",) + CUM_SM + CUM_GLOBAL + GAUGES
N_COUNTERS = len(COUNTERS)
# the columns that must equal stats.finalize totals in the final sample
FINAL_MATCH = CUM_SM + CUM_GLOBAL


def enabled(scfg) -> bool:
    """Static (Python-level) gate: telemetry changes the state pytree and
    the compiled program ONLY when the StaticConfig asks for samples."""
    return getattr(scfg, "telemetry_samples", 0) > 0


def init(scfg) -> dict:
    """The ``telem`` state part: preallocated sample buffer + write index
    + cumulative lockstep-waste accumulator.  Shapes depend only on the
    telemetry knobs, so the part is replicated under 'sm' sharding and
    vmaps over config/workload lanes like any other state."""
    return {
        "buf": jnp.zeros((scfg.telemetry_samples, N_COUNTERS), jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
        "waste": jnp.zeros((), jnp.int32),
    }


def _tot(x, axis_name):
    """Sum a (possibly device-local) per-SM array to a full-machine total:
    local sum, then psum over the mesh axis when sharded."""
    s = jnp.sum(x, dtype=jnp.int32)
    return jax.lax.psum(s, axis_name) if axis_name else s


def _row(telem: dict, state: dict, axis_name=None):
    """One (N_COUNTERS,) snapshot of the current cumulative counters."""
    vals = [state["ctrl"]["cycle"]]
    vals += [_tot(state["stats_sm"][k], axis_name) for k in CUM_SM]
    vals += [jnp.asarray(state["stats"][k], jnp.int32) for k in CUM_GLOBAL]
    vals.append(_tot(state["warp"]["active"], axis_name))
    vals.append(telem["waste"])
    return jnp.stack(vals)


def waste_increment(state: dict, n_instr, scfg, axis_name=None):
    """Lockstep waste accrued this quantum: Δ cycles for every SM with no
    live warps AND no in-flight memory requests (fully converged — nothing
    can wake it but the quantum barrier) while the kernel is not done."""
    warp = state["warp"]
    live = warp["active"] & ~((warp["pc"] >= n_instr)
                              & (warp["pending"] == 0))
    sm_live = jnp.any(live, axis=1)                       # (n_sm_local,)
    sm_busy = jnp.any(state["req"]["stage"] != 0, axis=1)
    idle = jnp.sum(~sm_live & ~sm_busy, dtype=jnp.int32)
    if axis_name:
        idle = jax.lax.psum(idle, axis_name)
    running = state["ctrl"]["done_cycle"] < 0
    return jnp.where(running, idle * scfg.quantum, 0)


def sample(telem: dict, state: dict, scfg, axis_name=None,
           force: bool = False) -> dict:
    """Maybe write a snapshot row.  Periodic samples fire every
    ``telemetry_every``-th quantum while the buffer has room; ``force``
    (end of kernel) always writes, overwriting the last slot when full —
    so the final written row is always the final cumulative counters."""
    n = scfg.telemetry_samples
    if force:
        do = jnp.ones((), jnp.bool_)
    else:
        q = state["ctrl"]["cycle"] // scfg.quantum
        do = (q % scfg.telemetry_every == 0) & (telem["idx"] < n)
    row = _row(telem, state, axis_name)
    pos = jnp.clip(telem["idx"], 0, n - 1)
    buf = telem["buf"].at[pos].set(
        jnp.where(do, row, telem["buf"][pos]))
    idx = jnp.minimum(telem["idx"] + jnp.where(do, 1, 0), n)
    return dict(telem, buf=buf, idx=idx)


def quantum_update(telem: dict, state: dict, trace: dict, scfg,
                   axis_name=None) -> dict:
    """Per-quantum telemetry step, called at the end of every quantum body
    (engine.quantum_step / the distributed kernel runners): accumulate
    lockstep waste, then take a periodic sample."""
    telem = dict(telem, waste=telem["waste"] + waste_increment(
        state, trace["n_instr"], scfg, axis_name))
    return sample(telem, state, scfg, axis_name)


# ---------------------------------------------------------------------------
# host-side extraction
# ---------------------------------------------------------------------------

def timeline(state: dict) -> np.ndarray:
    """The used rows of one lane's sample buffer as an (n_used, N_COUNTERS)
    numpy array (lane-sliced state: take_lane / take_grid_lane)."""
    telem = state["telem"]
    idx = int(np.asarray(telem["idx"]))
    return np.asarray(telem["buf"])[:idx]


def check_final_sample(state: dict, finalized: dict) -> list:
    """Names of FINAL_MATCH counters whose last timeline sample does NOT
    equal the finalize() total — empty list means the invariant holds."""
    tl = timeline(state)
    if tl.shape[0] == 0:
        return ["<no samples>"]
    last = tl[-1]
    return [name for name in FINAL_MATCH
            if int(last[COUNTERS.index(name)]) != int(finalized[name])]


# ---------------------------------------------------------------------------
# compile counters
# ---------------------------------------------------------------------------

# the stages of one compile, as JAX times them: tracing to a jaxpr,
# lowering to an MLIR module, and the backend compile, which spans the
# persistent-cache lookup and so holds a cache hit's retrieval too
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class _CompileCounters:
    """The process's compile work as JAX reports it: each stage's spans
    (wall-clock start, end), cache hits, misses and retrievals."""

    def __init__(self):
        self.lock = threading.Lock()
        self.spans = {stage: [] for stage in COMPILE_STAGES.values()}
        self.counts = dict.fromkeys(
            ("cache_hits", "cache_misses", "cache_retrievals"), 0)
        self.retrieval_s = 0.0

    def on_span(self, event: str, start: float, end: float, **_):
        stage = COMPILE_STAGES.get(event)
        if stage is not None:
            with self.lock:
                self.spans[stage].append((start, end))

    def on_event(self, event: str, **_):
        name = CACHE_EVENTS.get(event)
        if name is not None:
            with self.lock:
                self.counts[name] += 1

    def on_duration(self, event: str, secs: float, **_):
        if event == CACHE_RETRIEVAL:
            with self.lock:
                self.counts["cache_retrievals"] += 1
                self.retrieval_s += secs


# one set per process, as JAX's listeners are, counting from this import
_COMPILES = _CompileCounters()
jax.monitoring.register_event_time_span_listener(_COMPILES.on_span)
jax.monitoring.register_event_listener(_COMPILES.on_event)
jax.monitoring.register_event_duration_secs_listener(_COMPILES.on_duration)


def _covered(spans) -> float:
    """Seconds covered by the union of (start, end) spans: a jit traced
    inside another's trace nests in it and is not counted twice."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def compile_counters() -> dict:
    """Snapshot of the process's compile work since this module's import:
    ``<stage>_n`` events and ``<stage>_s`` seconds for each stage of
    ``COMPILE_STAGES``, ``cache_hits``, ``cache_misses``,
    ``cache_retrievals`` and ``cache_retrieval_s``."""
    c = _COMPILES
    with c.lock:
        spans = {k: list(v) for k, v in c.spans.items()}
        out = dict(c.counts, cache_retrieval_s=c.retrieval_s)
    for stage, sp in spans.items():
        out[f"{stage}_n"] = len(sp)
        out[f"{stage}_s"] = _covered(sp)
    return out


def compile_delta(before: dict, after: dict) -> dict:
    """The compile work between two ``compile_counters`` snapshots."""
    return {k: after[k] - before[k] for k in after}


# ---------------------------------------------------------------------------
# run manifests
# ---------------------------------------------------------------------------

MANIFEST_SCHEMA = 1


def runs_dir() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(here, "experiments", "runs")


def git_sha() -> str:
    sha = os.environ.get("GITHUB_SHA", "")
    if not sha:
        import subprocess
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True,
                text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
        except (OSError, subprocess.SubprocessError):
            sha = ""
    return sha or "unknown"


def static_hash(scfg) -> str:
    """Stable short hash of a StaticConfig — manifests from the same shape
    (hence the same compiled-program cache key) share it."""
    payload = json.dumps(asdict(scfg), sort_keys=True)
    return hashlib.sha1(payload.encode()).hexdigest()[:12]


def host_context() -> dict:
    """Where a run happened — hostname, device kind/count, the XLA flags
    that shape compilation.  Cross-machine BENCH/manifest comparisons are
    meaningless without this label."""
    import platform
    import socket

    ctx = {
        "hostname": socket.gethostname(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }
    try:
        devs = jax.devices()
        ctx["jax_version"] = jax.__version__
        ctx["device_platform"] = devs[0].platform
        ctx["device_kind"] = devs[0].device_kind
        ctx["device_count"] = len(devs)
    except Exception:  # noqa: BLE001 — jax may be unusable in odd envs
        ctx["device_platform"] = "unknown"
    return ctx


def write_manifest(kind: str, *, scfg=None, mesh_shape=None, timings=None,
                   stats=None, timelines=None, lanes=None, extra=None,
                   out_dir=None) -> str:
    """Write one structured run manifest JSON under experiments/runs/.

    ``stats``: list of finalized per-lane stat dicts (made JSON-safe via
    stats.to_jsonable).  ``timelines``: {lane_key: [[row], ...]} sampled
    counter timelines (column order = COUNTERS).  ``lanes``: per-lane
    descriptions (config knobs / workload names).  Returns the path.
    """
    from repro.core.stats import to_jsonable

    out_dir = out_dir or runs_dir()
    os.makedirs(out_dir, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    path = os.path.join(out_dir, f"{stamp}_{kind.replace('/', '_')}.json")
    # never silently overwrite a same-second manifest
    seq = 1
    while os.path.exists(path):
        path = os.path.join(out_dir,
                            f"{stamp}_{kind.replace('/', '_')}.{seq}.json")
        seq += 1
    payload = {
        "schema": MANIFEST_SCHEMA,
        "kind": kind,
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": git_sha(),
        "host": host_context(),
        "mesh_shape": list(mesh_shape) if mesh_shape else None,
        "timings": to_jsonable(timings or {}),
    }
    if scfg is not None:
        payload["static_config"] = to_jsonable(asdict(scfg))
        payload["static_config_hash"] = static_hash(scfg)
        payload["telemetry"] = {
            "samples": getattr(scfg, "telemetry_samples", 0),
            "every": getattr(scfg, "telemetry_every", 1),
            "counters": list(COUNTERS),
        }
    if lanes is not None:
        payload["lanes"] = to_jsonable(lanes)
    if stats is not None:
        payload["stats"] = to_jsonable(stats)
    if timelines is not None:
        payload["timelines"] = to_jsonable(timelines)
    if extra:
        payload.update(to_jsonable(extra))
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def write_job_manifest(job, *, scfg=None, out_dir=None) -> str:
    """Per-job manifest for the sim server (core/service.py): the job's
    identity, its per-lane ``finalize`` stats, and the latency split the
    serving story is about — how long the job queued vs how long its
    batch spent compiling vs executing.  Same schema/venue as every
    other run manifest (experiments/runs/), so report.py and
    cost_hints_from_manifests see served jobs like any other run."""
    return write_manifest(
        "serve_job", scfg=scfg, stats=job.stats,
        timings=dict(job.latency(), **{
            "n_lanes": job.n_lanes,
            "batch_lanes": (job.batch or {}).get("n_lanes"),
            "aot_cache": (job.batch or {}).get("aot_cache"),
        }),
        lanes=[{"workload": job.name}] * job.n_lanes,
        extra={"job": {"id": job.id, "seq": job.seq,
                       "batch": job.batch}},
        out_dir=out_dir)
