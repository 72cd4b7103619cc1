"""PR 8 batching bet: RunPlan, bucketed lane packing, ragged layout,
early exit, compile caching.

The acceptance property stays the grid one — every bucketed/ragged lane
bit-identical to its solo run (the mixed zoo+trace version lives in
tests/test_zoo_grid.py, riding the solo-verified monolithic grid) — plus
the PR's own observables: bucketing is deterministic and order-preserving,
an entry-converged padding kernel charges ZERO quanta, a warm sweep skips
lower+compile entirely, and the legacy flat kwargs still work (warn once).
"""
import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import pytest

import repro.core.plan as plan_mod
from repro.core import batch
from repro.core import stats as S
from repro.core.batch import (INSTR_FIELDS, SCALAR_FIELDS, bucket_workloads,
                              concat_kernels, split_ragged, workload_cost,
                              workload_shape)
from repro.core.engine import run_kernel
from repro.core.parallel import make_sm_runner
from repro.core.plan import (RunPlan, enable_persistent_cache, resolve_plan)
from repro.core.sweep import clear_aot_cache, sweep
from repro.sim.config import TINY, split_config
from repro.sim.state import init_state
from repro.sim.workloads import zoo_workload

MAX_CYCLES = 1 << 13
SCALE = 0.005


# ---------------------------------------------------------------------------
# RunPlan validation + legacy shim
# ---------------------------------------------------------------------------

def test_runplan_rejects_bad_knobs():
    for kw in (dict(mode="shard"), dict(exchange="bogus"),
               dict(bucket_by="size"), dict(layout="flat"),
               dict(max_cycles=0), dict(max_buckets=0),
               dict(telemetry_samples=-1), dict(telemetry_every=0)):
        with pytest.raises(ValueError):
            RunPlan(**kw)


def test_runplan_mesh_needs_cfg_sm_axes():
    import numpy as np
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    with pytest.raises(ValueError, match=r"\('cfg','sm'\) mesh"):
        RunPlan(mesh=mesh)


def test_resolve_plan_rejects_mixed_plan_and_legacy():
    with pytest.raises(ValueError, match="not both"):
        resolve_plan(RunPlan(), where="sweep", max_cycles=64)


def test_resolve_plan_rejects_non_plan():
    with pytest.raises(TypeError, match="must be a RunPlan"):
        resolve_plan({"max_cycles": 64}, where="sweep")


def test_resolve_plan_tolerates_old_positional_mode():
    assert resolve_plan("seq", where="sweep").mode == "seq"
    with pytest.raises(ValueError, match="mode given twice"):
        resolve_plan("seq", where="sweep", mode="vmap")


def test_legacy_kwargs_build_plan_and_warn_once(monkeypatch):
    monkeypatch.setattr(plan_mod, "_warned_legacy", False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        p = resolve_plan(None, where="sweep", max_cycles=64, mode="seq")
        resolve_plan(None, where="sweep", max_cycles=64)
    assert (p.max_cycles, p.mode) == (64, "seq")
    deps = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1 and "plan=RunPlan" in str(deps[0].message)


def test_runplan_describe_is_json_safe():
    json.dumps(RunPlan(bucket_by="cost", layout="ragged").describe())


# ---------------------------------------------------------------------------
# persistent compile cache wiring
# ---------------------------------------------------------------------------

def test_persistent_cache_idempotent_and_rewire_refused(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(plan_mod, "_persistent_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = enable_persistent_cache(str(tmp_path / "cache"))
    assert d == str(tmp_path / "cache")
    assert enable_persistent_cache(str(tmp_path / "cache")) == d
    with pytest.raises(ValueError, match="refusing to re-wire"):
        enable_persistent_cache(str(tmp_path / "elsewhere"))


def test_persistent_cache_env_dir_wins_and_default_is_fixed(tmp_path,
                                                            monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache: an explicit
    cache_dir does not override it and no other directory is set in code.
    Unset, the entry-point default is one fixed path in the checkout."""
    monkeypatch.setattr(plan_mod, "_persistent_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_persistent_cache(str(tmp_path / "plan")) == \
        str(tmp_path / "env")
    assert enable_persistent_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "plan").exists()
    assert plan_mod.DEFAULT_CACHE_DIR == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


# ---------------------------------------------------------------------------
# ragged concat (cu_seqlens idiom)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mixed_packs():
    w = zoo_workload("mixed", scale=SCALE)
    return [k.pack() for k in w.kernels]


def test_concat_kernels_offsets_and_shapes(mixed_packs):
    tr = concat_kernels(mixed_packs)
    lens = [int(p["n_instr"]) for p in mixed_packs]
    total = sum(lens)
    for f in INSTR_FIELDS:
        assert tr[f].shape[0] == total
    bases = [0]
    for n in lens[:-1]:
        bases.append(bases[-1] + n)
    assert [int(b) for b in tr["instr_base"]] == bases
    # the flat stream really is the kernels laid end to end
    for p, b in zip(mixed_packs, bases):
        assert jnp.array_equal(tr["ops"][b:b + int(p["n_instr"])], p["ops"])


def test_concat_kernels_padding_slots_are_inert(mixed_packs):
    k = len(mixed_packs)
    tr = concat_kernels(mixed_packs, n_kernels=k + 2)
    assert tr["n_ctas"].shape == (k + 2,)
    assert [int(v) for v in tr["n_ctas"][k:]] == [0, 0]
    # warps_per_cta pads with 1, never 0 — it divides in cta_issue
    assert [int(v) for v in tr["warps_per_cta"][k:]] == [1, 1]
    assert [int(v) for v in tr["instr_base"][k:]] == [0, 0]


def test_split_ragged_partition(mixed_packs):
    tr = concat_kernels(mixed_packs)
    scan_xs, flat = split_ragged(tr)
    assert set(scan_xs) == set(SCALAR_FIELDS) | {"instr_base"}
    assert set(flat) == set(INSTR_FIELDS)


# ---------------------------------------------------------------------------
# bucketing (pure host-side grouping)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def zoo_mix():
    return [zoo_workload(n, scale=SCALE)
            for n in ("gemm_tiled", "mixed", "reduction_tree",
                      "streaming_copy", "stencil")]


def test_bucket_none_is_single_identity_bucket(zoo_mix):
    groups = bucket_workloads(zoo_mix, by="none", max_buckets=4)
    assert groups == [list(range(len(zoo_mix)))]


def test_buckets_partition_and_respect_cap(zoo_mix):
    for by in ("shape", "cost"):
        for cap in (1, 2, 3, len(zoo_mix) + 3):
            groups = bucket_workloads(zoo_mix, by=by, max_buckets=cap)
            assert 1 <= len(groups) <= cap
            flat = sorted(i for g in groups for i in g)
            assert flat == list(range(len(zoo_mix)))
            # deterministic: same call, same grouping
            assert groups == bucket_workloads(zoo_mix, by=by,
                                              max_buckets=cap)


def test_shape_buckets_group_similar_lanes(zoo_mix):
    """Buckets split at the LARGEST shape gaps: every bucket's internal
    spread is no larger than the gap to the next bucket."""
    groups = bucket_workloads(zoo_mix, by="shape", max_buckets=3)
    keys = {i: workload_shape(w)[0] * workload_shape(w)[1]
            for i, w in enumerate(zoo_mix)}
    spans = [(min(keys[i] for i in g), max(keys[i] for i in g))
             for g in groups]
    spans.sort()
    for (lo_a, hi_a), (lo_b, _) in zip(spans, spans[1:]):
        assert hi_a <= lo_b      # buckets are contiguous key ranges


def test_cost_hint_overrides_instruction_count(zoo_mix):
    w = zoo_mix[0]
    default = workload_cost(w)
    assert default == sum(int(k.n_instr) * int(k.n_ctas)
                          for k in w.kernels)
    assert workload_cost(w, {w.name: 123.5}) == 123.5


def test_cost_hints_from_manifests(tmp_path):
    from repro.core.telemetry import COUNTERS
    wi = COUNTERS.index("lockstep_waste")
    tl = [[0.0] * len(COUNTERS), [0.0] * len(COUNTERS)]
    tl[-1][wi] = 40.0
    (tmp_path / "a.json").write_text(json.dumps({
        "stats": [{"workload": "mixed", "cycles": 100}],
        "timelines": {"mixed/0": tl}}))
    (tmp_path / "junk.json").write_text("{not json")
    hints = batch.cost_hints_from_manifests(str(tmp_path))
    assert hints["mixed"] == 140.0


# ---------------------------------------------------------------------------
# early exit: an entry-converged padding kernel charges ZERO quanta
# ---------------------------------------------------------------------------

def test_empty_kernel_runs_zero_quanta():
    scfg, dyn = split_config(TINY)
    w = zoo_workload("streaming_copy", scale=SCALE)
    tr = dict(w.kernels[0].pack())
    tr["n_ctas"] = jnp.zeros((), jnp.int32)   # a grid padding slot
    st = init_state(scfg)
    runner = make_sm_runner(scfg, "vmap")
    out = run_kernel(st, tr, scfg, dyn, runner, max_cycles=MAX_CYCLES,
                     early_exit=True)
    # zero while_loop iterations: the clock did not move, and done_cycle
    # was stamped at entry
    assert int(out["ctrl"]["cycle"]) == int(st["ctrl"]["cycle"])
    assert int(out["ctrl"]["done_cycle"]) == int(st["ctrl"]["cycle"])
    # without early exit the loop burns ≥1 full quantum discovering it
    out_slow = run_kernel(st, tr, scfg, dyn, runner, max_cycles=MAX_CYCLES,
                          early_exit=False)
    assert int(out_slow["ctrl"]["cycle"]) > int(st["ctrl"]["cycle"])


def test_real_kernel_never_entry_converged():
    scfg, dyn = split_config(TINY)
    w = zoo_workload("streaming_copy", scale=SCALE)
    from repro.core.engine import mark_entry_converged
    st = mark_entry_converged(init_state(scfg), w.kernels[0].pack())
    assert int(st["ctrl"]["done_cycle"]) == -1


# ---------------------------------------------------------------------------
# AOT executable cache: a warm sweep skips lower+compile
# ---------------------------------------------------------------------------

def test_sweep_aot_cache_warm_hit():
    clear_aot_cache()
    w = zoo_workload("streaming_copy", scale=SCALE)
    cfgs = [TINY, dataclasses.replace(TINY, scheduler="lrr")]
    plan = RunPlan(max_cycles=MAX_CYCLES)
    cold = sweep(w, cfgs, plan=plan)
    assert cold.timings["aot_cache"] == "miss"
    warm = sweep(w, cfgs, plan=plan)
    assert warm.timings["aot_cache"] == "hit"
    assert warm.timings["compile_s"] == 0.0
    for a, b in zip(cold.stats, warm.stats):
        assert S.comparable(a) == S.comparable(b)
    # a different plan knob is a different program: no false sharing
    other = sweep(w, cfgs, plan=RunPlan(max_cycles=MAX_CYCLES // 2))
    assert other.timings["aot_cache"] == "miss"
    clear_aot_cache()


# ---------------------------------------------------------------------------
# property backfill (hypothesis): choose_bucket_count / gap partition /
# cost_hints_from_manifests — the pure host-side planning layer
# ---------------------------------------------------------------------------

from collections import namedtuple  # noqa: E402
import random  # noqa: E402
import tempfile  # noqa: E402

from _hyp import given, settings, st  # noqa: E402
from repro.core.batch import choose_bucket_count  # noqa: E402

# plain ints (shim-safe: no strategy chaining when hypothesis is absent);
# every consumer treats them as the float keys they stand for
_keys = st.lists(st.integers(min_value=1, max_value=10**6),
                 min_size=1, max_size=24)

FakeKernel = namedtuple("FakeKernel", "name n_instr n_ctas warps_per_cta")
FakeWorkload = namedtuple("FakeWorkload", "name kernels")


def _fake_workloads(keys):
    """One single-kernel workload per key: shape key = 1 * n_instr and
    cost key = n_instr * 1 both equal the raw key, so one generator
    drives both policies."""
    return [FakeWorkload(f"w{i}", [FakeKernel(f"k{i}", int(k), 1, 1)])
            for i, k in enumerate(keys)]


@settings(max_examples=50, deadline=None)
@given(_keys)
def test_choose_bucket_count_bounds_and_order_free(keys):
    """k ∈ [1, min(max_k, n)], and the choice depends only on the key
    MULTISET — lane order can never change how many programs compile."""
    k = choose_bucket_count(keys)
    assert 1 <= k <= min(8, len(keys))
    assert k == choose_bucket_count(sorted(keys))
    assert k == choose_bucket_count(sorted(keys, reverse=True))


@settings(max_examples=50, deadline=None)
@given(_keys, st.integers(min_value=2, max_value=100))
def test_choose_bucket_count_scale_invariant(keys, c):
    """Rescaling every key (and so the default mean-cost overhead) by a
    constant changes no trade-off: same bucket count."""
    assert choose_bucket_count(keys) == \
        choose_bucket_count([k * c for k in keys])


@settings(max_examples=50, deadline=None)
@given(_keys)
def test_choose_bucket_count_gap_monotone(keys):
    """Bucket count is monotone in gap structure at the extremes: a
    zero-gap key multiset never splits, and stretching the largest gap
    wide enough never REDUCES the count."""
    assert choose_bucket_count([keys[0]] * len(keys)) == 1
    if len(set(keys)) > 1:
        base = choose_bucket_count(keys)
        lo = sorted(keys)[:len(keys) // 2 + 1]
        stretched = lo + [k * 10**4 for k in sorted(keys)[len(lo):]]
        assert choose_bucket_count(stretched) >= min(base, 2)


@settings(max_examples=50, deadline=None)
@given(_keys, st.integers(min_value=1, max_value=9),
       st.sampled_from(["shape", "cost"]))
def test_bucket_partition_covers_every_lane_once(keys, cap, by):
    """For any key multiset, cap and policy: the groups PARTITION
    range(n) — every lane index appears exactly once, ≤ cap groups, and
    each group spans a contiguous key range.  (This partition property
    is what makes sweep reassembly order-preserving: grid_sweep and
    pair_sweep write ``stats[i]`` by original lane index, so as long as
    every index appears exactly once, hints and bucketing can never
    reorder or drop a lane's result.)"""
    ws = _fake_workloads(keys)
    groups = bucket_workloads(ws, by=by, max_buckets=cap)
    flat = [i for g in groups for i in g]
    assert sorted(flat) == list(range(len(ws)))
    assert 1 <= len(groups) <= cap
    spans = sorted((min(keys[i] for i in g), max(keys[i] for i in g))
                   for g in groups)
    for (_, hi_a), (lo_b, _) in zip(spans, spans[1:]):
        assert hi_a <= lo_b


@settings(max_examples=50, deadline=None)
@given(_keys, st.integers(min_value=1, max_value=9))
def test_cost_hints_change_grouping_never_membership(keys, cap):
    """Hints may regroup lanes but never add, drop or duplicate one —
    and hints agreeing with the default cost change nothing at all."""
    ws = _fake_workloads(keys)
    plain = bucket_workloads(ws, by="cost", max_buckets=cap)
    wild = bucket_workloads(ws, by="cost", max_buckets=cap,
                            cost_hints={w.name: 1.0 + (i % 3)
                                        for i, w in enumerate(ws)})
    for groups in (plain, wild):
        assert sorted(i for g in groups for i in g) == \
            list(range(len(ws)))
    agree = bucket_workloads(ws, by="cost", max_buckets=cap,
                             cost_hints={w.name: workload_cost(w)
                                         for w in ws})
    assert agree == plain


@settings(max_examples=25, deadline=None)
@given(st.dictionaries(st.sampled_from(["gemm", "mixed", "stencil",
                                        "copy", "trace:x"]),
                       st.lists(st.integers(min_value=0,
                                            max_value=10**6),
                                min_size=1, max_size=4),
                       min_size=1, max_size=5),
       st.integers(min_value=0, max_value=2**32 - 1))
def test_cost_hints_from_manifests_order_free(costs, seed):
    """Harvested hints are the per-workload MAX over all manifest
    entries — identical whatever order the entries are written in,
    across files or within one (dict/file-order shuffling)."""
    entries = [(name, c) for name, cs in costs.items() for c in cs]
    rng = random.Random(seed)
    harvests = []
    for _ in range(2):
        rng.shuffle(entries)
        cut = rng.randrange(len(entries) + 1)
        with tempfile.TemporaryDirectory() as d:
            for fname, chunk in (("a.json", entries[:cut]),
                                 ("b.json", entries[cut:])):
                with open(f"{d}/{fname}", "w") as f:
                    json.dump({"stats": [
                        {"workload": n, "cycles": c}
                        for n, c in chunk]}, f)
            harvests.append(batch.cost_hints_from_manifests(d))
    want = {n: float(max(cs)) for n, cs in costs.items()}
    assert harvests[0] == harvests[1] == want
