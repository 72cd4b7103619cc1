"""Shared benchmark utilities."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(REPO, "experiments", "bench")

# paper-suite subset used by default (full list via --full)
DEFAULT_BENCHES = ["myocyte", "lavaMD", "hotspot", "sssp", "cut_1", "cut_2",
                   "gemm", "nw"]
SIM_SCALE = float(os.environ.get("REPRO_SIM_SCALE", "0.03"))
MAX_CYCLES = int(os.environ.get("REPRO_SIM_MAX_CYCLES", str(1 << 17)))


def grid_workload_names(n: int) -> list:
    """Workload rows for the grid benchmarks: ``REPRO_GRID_WORKLOADS``
    (comma-separated; zoo names, ``trace:<x>`` and Table-2 names all
    resolve via sim/workloads.py:resolve_workload) or the first ``n``
    zoo entries."""
    env = os.environ.get("REPRO_GRID_WORKLOADS", "")
    if env:
        return [s for s in (t.strip() for t in env.split(",")) if s]
    from repro.sim.workloads import zoo_names
    return zoo_names()[:n]


def timeit(fn, *args, warmup: int = 1, iters: int = 3):
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters


def save_json(name: str, payload: dict):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, name + ".json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)


def git_sha() -> str:
    sha = os.environ.get("GITHUB_SHA", "")
    if not sha:
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True,
                text=True, cwd=REPO, timeout=10).stdout.strip()
        except (OSError, subprocess.SubprocessError):
            sha = ""
    return sha or "unknown"


def save_bench(suite: str, rows: list) -> str:
    """Standardized perf-trajectory artifact: BENCH_<suite>.json with the
    suite's rows plus the git sha, UTC date and HOST CONTEXT (hostname,
    device kind/count, XLA_FLAGS — core/telemetry.py:host_context), so
    CI-uploaded artifacts are comparable across commits and labeled across
    machines.  Also drops a ``bench_<suite>`` run manifest under
    experiments/runs/ so `launch/report.py list|summarize` sees bench runs
    next to launcher runs.  Returns the BENCH file path."""
    import datetime

    from repro.core.telemetry import host_context, write_manifest

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"BENCH_{suite}.json")
    payload = {
        "suite": suite,
        "rows": [{"name": r["name"], "us_per_call": r["us_per_call"],
                  "derived": r["derived"]} for r in rows],
        "git_sha": git_sha(),
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "host": host_context(),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    write_manifest(f"bench_{suite}",
                   extra={"suite": suite, "rows": payload["rows"]})
    return path


def cpu_child_env(what: str) -> dict:
    """Environment for a child process that rehearses on forced CPU host
    devices (``--xla_force_host_platform_device_count``, a CPU-only
    recipe).  The child is pinned to the CPU, so it never asks for an
    accelerator.  Raises when THIS process runs on an accelerator: its
    numbers would then be CPU numbers under a device benchmark's name,
    and a parent that has touched the chip holds it."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} runs in child processes on forced CPU host devices, "
            f"a CPU-only rehearsal; this process runs on {backend}, so "
            "it is refused here (run the shapes in-process over the real "
            "devices instead)")
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.path.join(REPO, "src"))


def run_shard_worker(workload: str, devices: int, policy: str = "static",
                     exchange: str = "window", scale: float = SIM_SCALE,
                     timeout: int = 900) -> dict:
    """Run one sharded simulation in a subprocess with `devices` host
    devices (jax locks the device count per process).  CPU only
    (``cpu_child_env``)."""
    env = dict(cpu_child_env("the sharded-mode worker"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, "-m", "benchmarks.shard_worker",
           "--workload", workload, "--devices", str(devices),
           "--policy", policy, "--exchange", exchange,
           "--scale", str(scale), "--max-cycles", str(MAX_CYCLES)]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"shard worker failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])
