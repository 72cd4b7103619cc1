"""Telemetry invariants (core/telemetry.py).

The contract the observability layer must keep:

1. OFF is free: with ``telemetry_samples == 0`` (the default) the state
   pytree is unchanged — no ``telem`` part, so the compiled program and
   the committed determinism golden are untouched.
2. ON is invisible to timing: enabling telemetry leaves the
   ``comparable()`` stat subset bit-identical to the telemetry-off run
   (the golden), in every execution mode.
3. The last timeline sample IS the final state: for every lane, the
   forced end-of-kernel sample equals ``stats.finalize`` totals on every
   cumulative counter — across seq, vmap, grid-vmap and (subprocess,
   @slow) the 2-D ('cfg','sm') mesh.

Plus serialization (stats.to_jsonable) and manifest/report-CLI smoke.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import stats as S
from repro.core import telemetry as T
from repro.core.engine import simulate
from repro.core.parallel import make_sm_runner
from repro.core.sweep import grid_sweep, sweep, take_grid_lane, take_lane
from repro.sim import memsys
from repro.sim.config import TINY, split_config, static_part
from repro.sim.state import init_state
from repro.sim.workloads import zoo_workload

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX = 1 << 14
TELEM = dataclasses.replace(TINY, telemetry_samples=32, telemetry_every=2)


def tiny_workload(scale=0.02):
    return zoo_workload("mixed", scale=scale)


# ---------------------------------------------------------------------------
# 1. off is free
# ---------------------------------------------------------------------------

def test_off_state_pytree_unchanged():
    assert not T.enabled(static_part(TINY))
    st0 = init_state(TINY)
    assert "telem" not in st0
    # the run grows no state part, and the memory phase's parts keep
    # their leaves (its trip count lives in ``telem`` alone); finalize
    # grows no telemetry keys either
    st = simulate(tiny_workload(), TINY, make_sm_runner(TINY, "vmap"),
                  max_cycles=MAX)
    assert set(st) == set(st0)
    for part in ("req", "mem", "stats"):
        assert jax.tree_util.tree_structure(st[part]) == \
            jax.tree_util.tree_structure(st0[part]), part
    out = S.finalize(st)
    assert "lockstep_waste" not in out
    assert "mem_chunks" not in out
    assert "telemetry_samples" not in out


def test_on_state_has_telem_part():
    scfg = static_part(TELEM)
    assert T.enabled(scfg)
    st = init_state(TELEM)
    assert st["telem"]["buf"].shape == (32, T.N_COUNTERS)


# ---------------------------------------------------------------------------
# 2. on is invisible to timing (matches the committed golden)
# ---------------------------------------------------------------------------

def test_on_matches_determinism_golden():
    """Telemetry-on hotspot@0.02 must reproduce the committed golden's
    comparable() stats bit-exactly — sampling must not perturb timing."""
    from repro.workloads import make_workload
    golden_path = os.path.join(REPO, "tests", "golden",
                               "determinism_tiny.json")
    with open(golden_path) as f:
        golden = json.load(f)["hotspot@0.02"]
    w = make_workload("hotspot", scale=0.02)
    st = simulate(w, TELEM, make_sm_runner(TELEM, "vmap"),
                  max_cycles=1 << 15)
    assert S.comparable(S.finalize(st)) == golden


# ---------------------------------------------------------------------------
# 3. last sample == finalize totals, every mode / every lane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["seq", "vmap"])
def test_final_sample_matches_finalize(mode):
    w = tiny_workload()
    st = simulate(w, TELEM, make_sm_runner(TELEM, mode), max_cycles=MAX)
    out = S.finalize(st)
    assert out["telemetry_samples"] > 0
    assert T.check_final_sample(st, out) == []
    # the cycle column is monotonically nondecreasing
    tl = T.timeline(st)
    cyc = tl[:, T.COUNTERS.index("cycle")]
    assert (np.diff(cyc) >= 0).all()


def test_mem_chunks_counts_the_memory_phase_trips():
    """``mem_chunks`` reads ceil(due_L2 / K) + ceil(due_DRAM / K) summed
    over the quanta.  Sampled every quantum, each row's gain in the
    served-request counters is that quantum's due counts (every due row
    is served once), so the column is recomputed here from the other
    columns.  48 MSHR rows an SM make K = 128 < the 384-row table, and
    the mixed workload brings more than K due rows in some quantum."""
    cfg = dataclasses.replace(TINY, mshr_per_sm=48, telemetry_samples=1024,
                              telemetry_every=1)
    k = memsys.chunk_rows(static_part(cfg))
    assert k == 128 < cfg.n_sm * cfg.mshr_per_sm
    st = simulate(tiny_workload(), cfg, make_sm_runner(cfg, "vmap"),
                  max_cycles=MAX)
    out = S.finalize(st)
    assert T.check_final_sample(st, out) == []
    assert out["telemetry_samples"] < cfg.telemetry_samples  # none lost
    tl = T.timeline(st)
    col = {name: tl[:, T.COUNTERS.index(name)] for name in T.COUNTERS}
    due1 = np.diff(col["l2_hit"] + col["l2_miss"], prepend=0)
    due2 = np.diff(col["dram_req"], prepend=0)
    trips = -(-due1 // k) + -(-due2 // k)
    assert due1.max() > k                         # a multi-trip quantum
    assert np.array_equal(col["mem_chunks"], np.cumsum(trips))
    assert out["mem_chunks"] == col["mem_chunks"][-1] > 0


def test_seq_vmap_timelines_identical():
    w = tiny_workload()
    tls = {}
    for mode in ("seq", "vmap"):
        st = simulate(w, TELEM, make_sm_runner(TELEM, mode), max_cycles=MAX)
        tls[mode] = T.timeline(st)
    assert np.array_equal(tls["seq"], tls["vmap"])


def test_sweep_lanes_final_samples():
    """Vmapped config sweep: every lane carries its own timeline whose
    last row equals that lane's finalize totals."""
    cfgs = [TELEM, dataclasses.replace(TELEM, scheduler="lrr"),
            dataclasses.replace(TELEM, l2_lat=64)]
    res = sweep(tiny_workload(), cfgs, max_cycles=MAX)
    tls = res.timelines()
    assert set(tls) == {"0", "1", "2"}
    for i in range(len(cfgs)):
        lane = take_lane(res.state, i)
        assert T.check_final_sample(lane, res.stats[i]) == [], i
    # lanes with different configs produced different timelines
    assert not np.array_equal(tls["0"], tls["2"])


def test_grid_sweep_lanes_final_samples():
    ws = [zoo_workload("gemm_tiled", scale=0.02), tiny_workload()]
    cfgs = [TELEM, dataclasses.replace(TELEM, scheduler="lrr")]
    res = grid_sweep(ws, cfgs, max_cycles=MAX)
    assert set(res.timelines()) == {"gemm_tiled/0", "gemm_tiled/1",
                                    "mixed/0", "mixed/1"}
    for w in range(2):
        for c in range(2):
            lane = take_grid_lane(res.state, w, c)
            assert T.check_final_sample(lane, res.stats[w][c]) == [], (w, c)


def test_sweep_comparable_off_vs_on():
    """The same sweep with telemetry off/on: comparable() bit-identical,
    and timings report the compile/execute split."""
    cfgs_off = [TINY, dataclasses.replace(TINY, scheduler="lrr")]
    cfgs_on = [dataclasses.replace(c, telemetry_samples=16)
               for c in cfgs_off]
    w = tiny_workload()
    off = sweep(w, cfgs_off, max_cycles=MAX)
    on = sweep(w, cfgs_on, max_cycles=MAX)
    for i in range(2):
        assert S.comparable(off.stats[i]) == S.comparable(on.stats[i])
    for res in (off, on):
        assert res.timings["n_lanes"] == 2
        assert res.timings["execute_s"] > 0
    assert off.timelines() == {}


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json
    import numpy as np
    from repro.core import stats as S
    from repro.core import telemetry as T
    from repro.core.distribute import make_mesh
    from repro.core.sweep import grid_sweep, take_grid_lane
    from repro.sim.config import TINY
    from repro.sim.workloads import zoo_workload

    MAX = 1 << 14
    TELEM = dataclasses.replace(TINY, telemetry_samples=32,
                                telemetry_every=2)
    cfgs = [TELEM, dataclasses.replace(TELEM, scheduler="lrr")]
    ws = [zoo_workload(n, scale=0.02) for n in ("gemm_tiled", "mixed")]

    out = {}
    for label, mesh in (("nomesh", None), ("2x2", make_mesh(2, 2))):
        g = grid_sweep(ws, cfgs, mesh=mesh, max_cycles=MAX)
        bad = []
        for w in range(2):
            for c in range(2):
                lane = take_grid_lane(g.state, w, c)
                bad += [f"{w}/{c}:{n}" for n in
                        T.check_final_sample(lane, g.stats[w][c])]
        out[label] = {
            "bad": bad,
            "comparable": [S.comparable(g.stats[w][c])
                           for w in range(2) for c in range(2)],
            "timelines": {k: v.tolist()
                          for k, v in g.timelines().items()},
        }
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_mesh_final_samples_and_timelines_match_single_device():
    """2-D ('cfg','sm') mesh: per-lane final samples still equal finalize
    totals (psum over 'sm' sees the whole machine), and the full sampled
    timelines are bit-identical to the single-device run."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["nomesh"]["bad"] == []
    assert res["2x2"]["bad"] == []
    assert res["2x2"]["comparable"] == res["nomesh"]["comparable"]
    assert res["2x2"]["timelines"] == res["nomesh"]["timelines"]


# ---------------------------------------------------------------------------
# serialization + manifest/report smoke
# ---------------------------------------------------------------------------

def test_to_jsonable_roundtrip():
    import jax.numpy as jnp
    payload = {
        "a": np.int64(3), "b": np.arange(3), "c": (1, np.float32(2.5)),
        "d": {"nested": jnp.zeros((), jnp.int32)},
        "e": True, "f": np.bool_(False), "g": None, "h": "s",
    }
    out = json.loads(json.dumps(S.to_jsonable(payload)))
    assert out == {"a": 3, "b": [0, 1, 2], "c": [1, 2.5],
                   "d": {"nested": 0}, "e": True, "f": False,
                   "g": None, "h": "s"}
    # bools must stay bools (bool is an int subclass)
    assert out["e"] is True and out["f"] is False
    # full finalize output serializes (the *_per_sm int64 arrays)
    st = simulate(tiny_workload(), TINY, make_sm_runner(TINY, "vmap"),
                  max_cycles=MAX)
    json.dumps(S.to_jsonable(S.finalize(st)))


def test_manifest_write_and_report(tmp_path, capsys):
    from repro.launch.report import diff_stats, render_timeline
    cfgs = [TELEM, dataclasses.replace(TELEM, scheduler="lrr")]
    res = sweep(tiny_workload(), cfgs, max_cycles=MAX)
    path = T.write_manifest(
        "testrun", scfg=res.scfg, timings=res.timings, stats=res.stats,
        timelines={k: v.tolist() for k, v in res.timelines().items()},
        lanes=[{"scheduler": c.scheduler} for c in cfgs],
        out_dir=str(tmp_path))
    with open(path) as f:
        m = json.load(f)
    assert m["schema"] == T.MANIFEST_SCHEMA
    assert m["kind"] == "testrun"
    assert m["static_config_hash"] == T.static_hash(res.scfg)
    assert m["telemetry"]["counters"] == list(T.COUNTERS)
    assert {"hostname", "device_count"} <= set(m["host"])
    assert len(m["timelines"]) == 2 and len(m["stats"]) == 2
    # report: the timeline renderer verifies last-sample == finalize and
    # returns the mismatch count — 0 on a real manifest
    assert render_timeline(m) == 0
    txt = capsys.readouterr()  # sparkline output went to stdout
    # diff against itself: no comparable() differences
    assert diff_stats(m, m) == []
    del txt


def test_manifest_no_same_second_overwrite(tmp_path):
    a = T.write_manifest("x", out_dir=str(tmp_path))
    b = T.write_manifest("x", out_dir=str(tmp_path))
    assert a != b and os.path.exists(a) and os.path.exists(b)


def test_static_hash_stable_and_distinct():
    scfg = static_part(TINY)
    assert T.static_hash(scfg) == T.static_hash(static_part(TINY))
    assert T.static_hash(scfg) != T.static_hash(static_part(TELEM))


def test_launcher_flags_smoke():
    """dse --telemetry writes a manifest whose timelines verify (the
    acceptance-criteria path, minus the subprocess)."""
    from repro.launch import dse
    runs_before = set(os.listdir(T.runs_dir())) \
        if os.path.isdir(T.runs_dir()) else set()
    dse.main(["--n", "2", "--scale", "0.005", "--telemetry", "8",
              "--telemetry-every", "4", "--max-cycles", str(MAX)])
    new = [f for f in os.listdir(T.runs_dir())
           if f not in runs_before and f.endswith(".json")]
    assert new, "dse wrote no manifest"
    from repro.launch.report import render_timeline
    newest = max(new)
    with open(os.path.join(T.runs_dir(), newest)) as f:
        m = json.load(f)
    try:
        assert m["kind"] == "dse"
        assert render_timeline(m, out=open(os.devnull, "w")) == 0
    finally:
        for f in new:  # keep the repo's experiments/runs clean under test
            os.unlink(os.path.join(T.runs_dir(), f))
