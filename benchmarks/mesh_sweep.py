"""Mesh-shape throughput: configs/sec of a distributed grid sweep vs the
2-D ('cfg', 'sm') mesh shape (core/distribute.py).

On the CPU each mesh shape runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=<A*B>`` — jax locks the
host device count at first init, so forcing it per shape is the only way
to sweep shapes from one driver (same recipe as fig5's shard workers; see
benchmarks/README.md).  On an accelerator every shape runs in THIS
process over the real devices (a child could not get the chip this
process holds); a shape needing more devices than there are fails.
This container has one physical core, so forced
host devices time-slice it: the numbers establish the *trajectory
harness* (BENCH_mesh.json artifacts in CI) and prove every shape runs;
real scaling needs real devices.  Lane results are bit-exact at every
shape regardless (tests/test_mesh_sweep.py), so the cheap shapes here are
trustworthy stand-ins for the expensive ones.

  python -m benchmarks.mesh_sweep                 # driver: sweep shapes
  python -m benchmarks.mesh_sweep --worker 2 2    # one shape (subprocess)
"""
from __future__ import annotations

import json
import subprocess
import sys
import time

from benchmarks.common import REPO, SIM_SCALE, cpu_child_env, save_json

MESH_SHAPES = ((1, 1), (2, 1), (1, 2), (2, 2), (4, 1))
N_WORKLOADS = 2
N_CONFIGS = 4
MAX_CYCLES = 1 << 14


def bench_one(n_cfg: int, n_sm: int) -> dict:
    """One grid sweep on one mesh shape: build the compiled runner ONCE,
    then time repeated calls of it — ``grid_sweep()`` itself builds a
    fresh jit closure per call, so timing it would re-pay compilation
    every iteration and report compile-dominated noise as throughput."""
    import jax

    from repro.core import distribute
    from repro.core.batch import stack_workloads
    from repro.core.sweep import batched_init, make_grid_runner, stack_dyn
    from repro.launch.dse import default_grid
    from repro.sim.config import TINY
    from repro.sim.workloads import zoo_names, zoo_workload

    workloads = [zoo_workload(n, scale=SIM_SCALE)
                 for n in zoo_names()[:N_WORKLOADS]]
    cfgs = default_grid(TINY, N_CONFIGS)
    scfg, dyn_batch = stack_dyn(cfgs)
    stacked = stack_workloads(workloads)
    mesh = None
    if (n_cfg, n_sm) == (1, 1):
        runner = make_grid_runner(scfg, max_cycles=MAX_CYCLES)
    else:
        mesh = distribute.make_mesh(n_cfg, n_sm)
        distribute.check_mesh(mesh, scfg, len(cfgs))
        dyn_batch = distribute.place_lanes(dyn_batch, mesh)
        stacked = distribute.place_lanes(
            stacked, mesh, jax.sharding.PartitionSpec())
        runner = distribute.make_dist_grid_runner(scfg,
                                                  max_cycles=MAX_CYCLES,
                                                  mesh=mesh)

    def fresh_state():
        # the runners DONATE the state batch, so every call gets its own
        st = batched_init(scfg, N_WORKLOADS, N_CONFIGS)
        if mesh is not None:
            st = distribute.place_state(st, mesh, None, distribute.CFG_AXIS)
        return st

    t0 = time.perf_counter()
    state = jax.block_until_ready(runner(fresh_state(), stacked, dyn_batch))
    compile_and_run = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = jax.block_until_ready(runner(fresh_state(), stacked, dyn_batch))
    wall = time.perf_counter() - t0
    lanes = N_WORKLOADS * N_CONFIGS
    return {
        "mesh": [n_cfg, n_sm], "lanes": lanes, "wall_s": wall,
        "compile_s": max(0.0, compile_and_run - wall),
        "lanes_per_s": lanes / max(wall, 1e-9),
        "cycles_check": int(state["ctrl"]["total_cycles"].sum()),
    }


def worker(n_cfg: int, n_sm: int) -> None:
    """Runs inside the subprocess with the forced device count."""
    print(json.dumps(bench_one(n_cfg, n_sm)))


def run_mesh_worker(n_cfg: int, n_sm: int, timeout: int = 1200) -> dict:
    env = dict(
        cpu_child_env("the mesh-shape worker"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_cfg * n_sm}")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.mesh_sweep",
         "--worker", str(n_cfg), str(n_sm)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"mesh worker failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(shapes=MESH_SHAPES, fast: bool = False) -> list[dict]:
    import jax

    if fast:  # honor run.py --fast: NO subprocess sweeps — just the
        shapes = ((1, 1),)  # in-process single-device anchor
    in_process = fast or jax.default_backend() != "cpu"
    rows = []
    results = {}
    checks = set()
    for a, b in shapes:
        r = bench_one(a, b) if in_process else run_mesh_worker(a, b)
        results[f"{a}x{b}"] = r
        checks.add(r["cycles_check"])
        rows.append({"name": f"mesh/grid_{a}x{b}",
                     "us_per_call": r["wall_s"] * 1e6,
                     "derived": (f"lanes_per_s={r['lanes_per_s']:.2f};"
                                 f"compile_s={r['compile_s']:.1f}")})
    # every shape must agree on total simulated cycles (cheap cross-check;
    # the bit-exact per-lane lock lives in tests/test_mesh_sweep.py)
    assert len(checks) <= 1, f"mesh shapes disagree on cycles: {results}"
    save_json("mesh_sweep", {
        "n_workloads": N_WORKLOADS, "n_configs": N_CONFIGS,
        "scale": SIM_SCALE, "max_cycles": MAX_CYCLES, "results": results,
    })
    return rows


if __name__ == "__main__":
    if "--worker" in sys.argv:
        i = sys.argv.index("--worker")
        worker(int(sys.argv[i + 1]), int(sys.argv[i + 2]))
    else:
        for row in run(fast="--fast" in sys.argv):
            print(row)
