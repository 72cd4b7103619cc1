"""Serving bench: the continuously batched sim server vs everything else.

Measures the ROADMAP's simulation-as-a-service claim on real numbers:

  · cold server — first batch pays lower+compile for its buckets
  · warm server (threaded, production shape) — jobs/sec and the p50/p99
    end-to-end job latency (queue + execute; compile amortized away)
  · one-process-per-job — the same jobs each run in a fresh python
    process (interpreter + jax import + compile per job), the way
    pre-service users ran sweeps

The ``speedup`` ratio pinned by benchmarks/perf_reference.json (entry
``serving``, file serving.json) is one-process-per-job wall over warm-
server wall on the SAME job list — both sides timed on this host in this
run, so machine speed cancels.  REPRO_SERVE_PERJOB_JOBS trims how many
subprocess jobs the baseline pays for (default 3; each one recompiles).
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import REPO, SIM_SCALE, cpu_child_env, save_json

SERVE_CYCLES = 1 << 15
JOB_NAMES = ["mixed", "reduction_tree", "streaming_copy", "trace:vecadd",
             "gemm_tiled", "stencil"]


def _subs() -> list:
    subs = []
    for i, name in enumerate(JOB_NAMES):
        s = {"id": f"j{i}", "workload": name}
        if not name.startswith("trace:"):
            s["scale"] = SIM_SCALE
        if i % 3 == 1:       # a config-override lane in the mix
            s["config"] = {"l2_lat": 64, "scheduler": "lrr"}
        subs.append(s)
    return subs


def _perjob_subprocess(sub: dict) -> float:
    """One job, one fresh process: build_job admission + solo simulate,
    paying interpreter start, jax import and compile — the pre-service
    cost model.  The child runs on the CPU, and only when this process
    does too (``cpu_child_env``).  Returns the wall-clock of the whole
    process."""
    code = (
        "from repro.core.engine import simulate\n"
        "from repro.core.parallel import make_sm_runner\n"
        "from repro.core.plan import RunPlan\n"
        "from repro.core.service import build_job\n"
        "from repro.sim.config import TINY, split_config\n"
        f"job = build_job({sub!r}, TINY, split_config(TINY)[0], 1)\n"
        "for w, cfg in job.pairs:\n"
        "    simulate(w, cfg, make_sm_runner(cfg, 'vmap'),\n"
        f"             plan=RunPlan(max_cycles={SERVE_CYCLES}))\n"
    )
    env = cpu_child_env("the one-process-per-job baseline")
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=1800)
    dt = time.perf_counter() - t0
    if out.returncode != 0:
        raise RuntimeError(f"per-job worker failed: {out.stderr[-2000:]}")
    return dt


def run() -> list:
    from repro.core.plan import RunPlan
    from repro.core.service import SimService
    from repro.core.sweep import clear_aot_cache
    from repro.sim.config import TINY

    plan = RunPlan(max_cycles=SERVE_CYCLES, bucket_by="shape")
    subs = _subs()
    n = len(subs)

    # -- cold: a fresh server compiles its buckets on the first batch ----
    clear_aot_cache()
    svc = SimService(base=TINY, plan=plan, start=False)
    t0 = time.perf_counter()
    for s in subs:
        svc.submit(s)
    while svc.run_pending():
        pass
    cold_s = time.perf_counter() - t0

    # -- warm, threaded: the production shape — jobs/sec and latency ----
    warm_svc = SimService(base=TINY, plan=plan, batch_lanes=4,
                          max_wait_s=0.01, start=True)
    t0 = time.perf_counter()
    jobs = [warm_svc.submit(s) for s in subs]
    assert warm_svc.drain(timeout=600.0), warm_svc.stats()
    warm_s = time.perf_counter() - t0
    warm_svc.shutdown(drain=False)
    lat = [j.latency()["total_s"] for j in jobs]
    p50, p99 = (float(np.percentile(lat, q)) for q in (50, 99))
    jobs_per_s = n / max(warm_s, 1e-9)

    # -- one-process-per-job baseline vs warm server, same K jobs -------
    k = max(1, int(os.environ.get("REPRO_SERVE_PERJOB_JOBS", "3")))
    ratio_subs = subs[:k]
    perjob_s = sum(_perjob_subprocess(s) for s in ratio_subs)
    t0 = time.perf_counter()
    for s in ratio_subs:
        svc.submit(s)
    while svc.run_pending():
        pass
    server_k_s = time.perf_counter() - t0
    speedup = perjob_s / max(server_k_s, 1e-9)

    save_json("serving", {
        "speedup": round(speedup, 3),
        "jobs": n, "ratio_jobs": k,
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "jobs_per_s_warm": round(jobs_per_s, 3),
        "p50_s": round(p50, 4), "p99_s": round(p99, 4),
        "perjob_s": round(perjob_s, 3),
        "server_k_s": round(server_k_s, 3),
    })
    us = 1e6
    return [
        {"name": "serve_cold_batch", "us_per_call": cold_s / n * us,
         "derived": f"{n} jobs, compile included"},
        {"name": "serve_warm_batch", "us_per_call": warm_s / n * us,
         "derived": f"{jobs_per_s:.2f} jobs/s, p50 {p50:.3f}s, "
                    f"p99 {p99:.3f}s"},
        {"name": "one_process_per_job", "us_per_call": perjob_s / k * us,
         "derived": f"{k} fresh processes"},
        {"name": "server_vs_perjob", "us_per_call": server_k_s / k * us,
         "derived": f"{speedup:.1f}x warm server vs per-job"},
    ]


if __name__ == "__main__":
    for row in run():
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
