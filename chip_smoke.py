"""Smoke run of the simulator's main path on TPU, at the full 80-SM
RTX 3080 Ti model, through the entry points a user calls.

  python3 chip_smoke.py               # phases a-d, one chip
  python3 chip_smoke.py --four-chips  # phases a and e: the 2-D mesh

Phases, in one process (they share its compile cache):

  a. device      platform, kind and count as JAX reports them; anything
                 but a TPU fails here, before any simulation runs.
  b. golden      every case of the determinism matrix
                 (tests/test_determinism_matrix.py CASES) in seq and vmap
                 mode, compared with tests/golden/determinism_tiny.json,
                 which the CPU produced.
  c. long run    hotspot at scale 1.0 (4 kernels x 1,024 CTAs) on the
                 80-SM model through repro.launch.simulate.run_simulation
                 in vmap mode; no kernel may time out, and the comparable
                 stats must equal tests/golden/hotspot_3080ti.json, the
                 same run on the CPU, written by
                   PYTHONPATH=src JAX_PLATFORMS=cpu python -m \\
                     repro.launch.simulate --workload hotspot --scale 1.0 \\
                     --max-cycles 131072 --out tests/golden/hotspot_3080ti.json
  d. served      a SimService(base=RTX3080TI, start=False) answers a zoo
                 job, an uploaded trace and a config-override job; every
                 served lane must finish (no kernel timed out) and equal a
                 solo simulate() on this device.
  e. four chips  (--four-chips only) grid_sweep at RTX3080TI width on the
                 ('cfg','sm') meshes 4x1, 1x4 and 2x2 over the four chips,
                 every lane compared with the same grid on one device, and
                 the placed state checked to span the mesh's devices.

Every phase prints its own lines; a phase that fails prints its traceback
on stderr and the script exits nonzero.  Only when every phase passed is
the last line of stdout the JSON object
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)           # tests/ (the determinism matrix cases)

HOTSPOT_REF = os.path.join(ROOT, "tests", "golden", "hotspot_3080ti.json")
VECADD_TRACE = os.path.join(ROOT, "tests", "data", "traces", "vecadd.trace")


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def signature(stats: dict) -> dict:
    """What every comparison here checks: the cross-mode comparable stats
    plus the truncation counter."""
    from repro.core import stats as S
    return dict(S.comparable(stats), timeouts=stats["timeouts"])


def diff_keys(got: dict, want: dict) -> list:
    return sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_golden() -> None:
    from tests.test_determinism_matrix import (CASES, load_case,
                                               load_golden, run_mode)

    golden = load_golden()
    bad = []
    for bench, scale in CASES:
        w = load_case(bench, scale)
        want = golden[f"{bench}@{scale}"]
        for mode in ("seq", "vmap"):
            t0 = time.perf_counter()
            got = run_mode(w, mode)
            keys = diff_keys(got, want)
            say(f"b {bench}@{scale} {mode}: "
                f"{'OK' if not keys else 'MISMATCH ' + ','.join(keys)} "
                f"cycles={got['cycles']} wall_s={time.perf_counter() - t0}")
            if keys:
                bad.append(f"{bench}@{scale}/{mode}: {keys}")
    assert not bad, f"golden mismatches: {bad}"


def phase_long_run() -> None:
    from repro.launch.simulate import run_simulation
    from repro.sim.config import RTX3080TI
    from repro.workloads import make_workload

    with open(HOTSPOT_REF) as f:
        ref = json.load(f)
    w = make_workload(ref["workload"], scale=ref["scale"])
    out, tm = run_simulation(w, RTX3080TI, ref["mode"], ref["max_cycles"])
    got = signature(out)
    want = dict(ref["stats"], timeouts=ref["timeouts"])
    say(f"c {w.name}@{ref['scale']} n_sm={RTX3080TI.n_sm} {ref['mode']}: "
        f"compile_s={tm['compile_s']} execute_s={tm['execute_s']} "
        f"cycles={out['cycles']} warp_instructions={out['issued']} "
        f"timeouts={out['timeouts']}")
    assert out["timeouts"] == 0, f"{out['timeouts']} kernel(s) timed out"
    keys = diff_keys(got, want)
    assert not keys, f"differs from the CPU reference in {keys}: " \
        f"{ {k: (got.get(k), want.get(k)) for k in keys} }"
    say("c matches the CPU reference")


def phase_served() -> None:
    from repro.core import stats as S
    from repro.core.engine import simulate
    from repro.core.parallel import make_sm_runner
    from repro.core.plan import RunPlan
    from repro.core.service import SimService
    from repro.sim.config import RTX3080TI

    svc = SimService(base=RTX3080TI, start=False)
    with open(VECADD_TRACE) as f:
        trace_text = f.read()
    jobs = [svc.submit(s) for s in (
        {"id": "zoo", "workload": "mixed", "scale": 0.25},
        {"id": "upload", "trace_text": trace_text},
        {"id": "override", "workload": "mixed", "scale": 0.25,
         "config": {"l2_lat": 64, "scheduler": "lrr"}},
    )]
    t0 = time.perf_counter()
    served = svc.run_pending()
    say(f"d served {served} jobs in one batch: {jobs[0].batch} "
        f"wall_s={time.perf_counter() - t0}")
    assert served == len(jobs), f"served {served}/{len(jobs)}"
    solo_plan = RunPlan(max_cycles=svc.plan.max_cycles)
    bad = []
    for job in jobs:
        assert job.done and job.error is None, job.response()
        for (w, cfg), st in zip(job.pairs, job.stats):
            solo = signature(S.finalize(simulate(
                w, cfg, make_sm_runner(cfg, "vmap"), plan=solo_plan)))
            keys = diff_keys(signature(st), solo)
            say(f"d job {job.id} ({w.name}): "
                f"{'OK' if not keys else 'MISMATCH ' + ','.join(keys)} "
                f"cycles={st['cycles']} timeouts={st['timeouts']}")
            if keys or st["timeouts"]:
                bad.append(f"{job.id}: {keys} timeouts={st['timeouts']}")
    assert not bad, f"served lanes differ from solo runs or time out: {bad}"


def phase_mesh() -> None:
    import jax

    from repro.core import distribute
    from repro.core.plan import RunPlan
    from repro.core.sweep import batched_init, grid_sweep
    from repro.launch.dse import default_grid
    from repro.sim.config import RTX3080TI
    from repro.sim.workloads import zoo_workload

    workloads = [zoo_workload("mixed", scale=0.25),
                 zoo_workload("trace:vecadd")]
    cfgs = default_grid(RTX3080TI, 4)
    max_cycles = 1 << 15
    t0 = time.perf_counter()
    ref = grid_sweep(workloads, cfgs, plan=RunPlan(max_cycles=max_cycles))
    timeouts = sum(s["timeouts"] for row in ref.stats for s in row)
    say(f"e one device: {ref.n_workloads}x{ref.n_cfgs} lanes "
        f"timeouts={timeouts} "
        f"compile_s={ref.timings['compile_s']} "
        f"execute_s={ref.timings['execute_s']} "
        f"wall_s={time.perf_counter() - t0}")
    bad = []
    for shape in ((4, 1), (1, 4), (2, 2)):
        mesh = distribute.make_mesh(*shape)
        devs = set(mesh.devices.flat)
        assert len(devs) == 4, f"mesh {shape} spans {len(devs)} devices"
        # placement: the per-SM state is split over 'sm', the lanes over
        # 'cfg', and every shard sits on its own mesh device
        placed = distribute.place_state(
            batched_init(ref.scfg, len(workloads), len(cfgs)), mesh, None,
            distribute.CFG_AXIS)
        for part in ("warp", "mem"):
            leaf = jax.tree_util.tree_leaves(placed[part])[0]
            assert leaf.sharding.device_set == devs, (part, shape)
            shard = leaf.addressable_shards[0].data.shape
            want = (len(workloads), len(cfgs) // shape[0]) + (
                (RTX3080TI.n_sm // shape[1],) if part == "warp" else ())
            assert shard[:len(want)] == want, (part, shape, shard, want)
        t0 = time.perf_counter()
        grid = grid_sweep(workloads, cfgs,
                          plan=RunPlan(max_cycles=max_cycles, mesh=mesh))
        for _, bstate in grid.buckets:
            for leaf in jax.tree_util.tree_leaves(bstate):
                assert leaf.sharding.device_set == devs, shape
        n_bad = 0
        for w in range(grid.n_workloads):
            for c in range(grid.n_cfgs):
                keys = diff_keys(signature(grid.stats[w][c]),
                                 signature(ref.stats[w][c]))
                if keys:
                    n_bad += 1
                    bad.append(f"{shape} {grid.names[w]}/{c}: {keys}")
        say(f"e mesh {shape[0]}x{shape[1]}: "
            f"{grid.n_workloads * grid.n_cfgs - n_bad}/"
            f"{grid.n_workloads * grid.n_cfgs} lanes bit-identical, "
            f"compile_s={grid.timings['compile_s']} "
            f"execute_s={grid.timings['execute_s']} "
            f"wall_s={time.perf_counter() - t0}")
    assert not bad, f"mesh lanes differ from the one-device grid: {bad}"


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ('cfg','sm') mesh phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"a device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if device["platform"] != "tpu":
        print("[chip_smoke] FAIL: no TPU; this script never runs on "
              f"{device['platform']}", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if device["count"] < need:
        print(f"[chip_smoke] FAIL: needs {need} chips, JAX sees "
              f"{device['count']}", file=sys.stderr)
        return 2

    from repro.core.plan import enable_persistent_cache
    say(f"compile cache: {enable_persistent_cache()}")

    phases = ([("e", phase_mesh)] if args.four_chips else
              [("b", phase_golden), ("c", phase_long_run),
               ("d", phase_served)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            failed.append(name)
        say(f"phase {name} {'FAILED' if name in failed else 'passed'} "
            f"in {time.perf_counter() - t0} s")
    if failed:
        print(f"[chip_smoke] FAIL: phase(s) {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
