"""The SM cycle's one-hot formulation against the scatter-based one it
replaced, field for field.

``sim/smcore.py`` reads and writes SM-local state by one-hot masks and
makes every sub-core's scheduler pick before the first issues.  The
oracle below is the earlier formulation, kept verbatim as a reference:
scalar-indexed reads, ``.at[...].set`` / ``.add`` writes, one sub-core
after another.  Both run one cycle, and a few cycles in a row, on batches
of seeded random SM states, vmapped over the SMs as the engine runs
them, and every field of the warp, SM, request and stats state must
agree.  Each scenario also checks that its batch reached what it is
there to cover (a full MSHR table, an L1 eviction, address-set
overflow, ...), so a generator that drifts cannot pass vacuously.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import smcore
from repro.sim.config import (BAR, FP32, LDG, N_CLASSES, N_UNITS, SCHED_GTO,
                              STG, GPUConfig, UNIT_OF_CLASS, split_config)
from repro.sim.trace import A_RANDOM, A_STREAM, A_STRIDED, gen_address

BIG = jnp.int32(1 << 30)


# --- the oracle: the scatter-based formulation ------------------------------

def _deliver(warp, req, t):
    done = (req["stage"] == 3) & (req["t"] <= t)
    dec = jnp.zeros_like(warp["pending"]).at[req["warp"]].add(
        jnp.where(done & ~req["is_store"], 1, 0))
    warp = dict(warp, pending=warp["pending"] - dec)
    req = dict(req, stage=jnp.where(done, 0, req["stage"]))
    return warp, req


def _l1_access(sm, addr, t, cfg):
    st = (addr % cfg.l1_sets).astype(jnp.int32)
    ways = sm["l1_tag"][st]
    hit = jnp.any(ways == addr)
    hway = jnp.argmax(ways == addr)
    victim = jnp.argmin(sm["l1_lru"][st])
    way = jnp.where(hit, hway, victim)
    l1_tag = sm["l1_tag"].at[st, way].set(
        jnp.where(hit, sm["l1_tag"][st, way], addr))
    l1_lru = sm["l1_lru"].at[st, way].set(t)
    return hit, dict(sm, l1_tag=l1_tag, l1_lru=l1_lru)


def _addrset_insert(sm, addr, enable, cfg):
    cap = cfg.addrset_cap
    aset = sm["addrset"]
    idx = (addr.astype(jnp.uint32) * jnp.uint32(2654435761)
           % jnp.uint32(cap)).astype(jnp.int32)
    inserted = ~enable
    for probe in range(4):
        slot = (idx + probe) % cap
        cur = aset[slot]
        can = (~inserted) & ((cur == addr) | (cur == -1))
        aset = aset.at[slot].set(jnp.where(can & (cur == -1), addr, cur))
        inserted = inserted | can
    over = jnp.where(~inserted, 1, 0)
    return dict(sm, addrset=aset, addrset_over=sm["addrset_over"] + over)


def _issue_subcore(warp, sm, req, stats, trace, t, sc, cfg, dyn):
    nsc = cfg.n_subcores
    w_ids = jnp.arange(sc, cfg.warps_per_sm, nsc, dtype=jnp.int32)
    pc = warp["pc"][w_ids]
    active = warp["active"][w_ids]
    n_instr = trace["n_instr"]
    exists = active & (pc < n_instr)
    blocked = (warp["wait_mem"][w_ids] & (warp["pending"][w_ids] > 0)) \
        | warp["wait_bar"][w_ids]
    ready = exists & ~blocked & (warp["ready_at"][w_ids] <= t)
    base = trace["instr_base"] if "instr_base" in trace else 0
    pcc = jnp.clip(pc, 0, n_instr - 1)
    op = trace["ops"][base + pcc]
    unit = jnp.asarray(UNIT_OF_CLASS, jnp.int32)[op]
    ufree = sm["unit_free"][sc][unit] <= t
    is_mem = (op == LDG) | (op == STG)
    free_rows = jnp.sum(req["stage"] == 0) > 0
    cand = ready & ufree & (~is_mem | free_rows)
    greedy = w_ids == sm["last_issued"][sc]
    key_gto = jnp.where(greedy, -1, w_ids)
    key_lrr = (w_ids - sm["last_issued"][sc] - 1) % cfg.warps_per_sm
    key = jnp.where(dyn.core.sched == SCHED_GTO, key_gto, key_lrr)
    key = jnp.where(cand, key, BIG)
    sel = jnp.argmin(key)
    do = cand[sel]
    wsel = w_ids[sel]
    spc = pcc[sel]
    sop = op[sel]
    sunit = unit[sel]

    gwarp = warp["cta"][wsel] * trace["warps_per_cta"] + warp["wic"][wsel]
    addr = gen_address(trace["addr_mode"][base + spc],
                       trace["addr_param"][base + spc],
                       gwarp, spc, cfg.mem_blocks)
    mem_issue = do & (sop == LDG) | (do & (sop == STG))
    hit, sm_new = _l1_access(sm, addr, t, cfg)
    sm = jax.tree_util.tree_map(
        lambda a, b: jnp.where(mem_issue, b, a), sm, sm_new)
    sm = _addrset_insert(sm, addr, mem_issue, cfg)
    l1_hit = mem_issue & hit
    l1_miss = mem_issue & ~hit

    row = jnp.argmin(jnp.where(req["stage"] == 0, 0, 1))
    alloc = l1_miss
    req = dict(
        req,
        stage=req["stage"].at[row].set(
            jnp.where(alloc, 1, req["stage"][row])),
        addr=req["addr"].at[row].set(
            jnp.where(alloc, addr, req["addr"][row])),
        t=req["t"].at[row].set(
            jnp.where(alloc, t + dyn.icnt.icnt_lat, req["t"][row])),
        warp=req["warp"].at[row].set(
            jnp.where(alloc, wsel, req["warp"][row])),
        is_store=req["is_store"].at[row].set(
            jnp.where(alloc, sop == STG, req["is_store"][row])),
    )

    lat = dyn.core.lat[sop]
    lat = jnp.where(sop == LDG, jnp.where(hit, dyn.cache.l1_hit_lat, 1), lat)
    dep_next = jnp.where(spc + 1 < n_instr, trace["dep"][
        base + jnp.clip(spc + 1, 0, n_instr - 1)], False)
    wait_lat = jnp.where(dep_next, jnp.maximum(lat, 1), 1)
    new_ready = t + wait_lat
    new_wait = dep_next & l1_miss
    new_pending = warp["pending"][wsel] + jnp.where(
        l1_miss & (sop == LDG), 1, 0)

    warp = dict(
        warp,
        pc=warp["pc"].at[wsel].set(jnp.where(do, spc + 1, warp["pc"][wsel])),
        ready_at=warp["ready_at"].at[wsel].set(
            jnp.where(do, new_ready, warp["ready_at"][wsel])),
        wait_mem=warp["wait_mem"].at[wsel].set(
            jnp.where(do, new_wait, warp["wait_mem"][wsel])),
        wait_bar=warp["wait_bar"].at[wsel].set(
            jnp.where(do & (sop == BAR), True, warp["wait_bar"][wsel])),
        pending=warp["pending"].at[wsel].set(
            jnp.where(do, new_pending, warp["pending"][wsel])),
    )
    disp = dyn.core.disp[sop]
    sm = dict(
        sm,
        unit_free=sm["unit_free"].at[sc, sunit].set(
            jnp.where(do, t + disp, sm["unit_free"][sc, sunit])),
        last_issued=sm["last_issued"].at[sc].set(
            jnp.where(do, wsel, sm["last_issued"][sc])),
    )
    stats = dict(
        stats,
        issued=stats["issued"] + jnp.where(do, 1, 0),
        issued_mem=stats["issued_mem"] + jnp.where(mem_issue, 1, 0),
        l1_hit=stats["l1_hit"] + jnp.where(l1_hit, 1, 0),
        l1_miss=stats["l1_miss"] + jnp.where(l1_miss, 1, 0),
        stall=stats["stall"] + jnp.where(jnp.any(exists) & ~do, 1, 0),
    )
    return warp, sm, req, stats, do


def oracle_cycle(warp, sm, req, stats, trace, t, cfg, dyn):
    warp, req = _deliver(warp, req, t)
    # the barrier release is the same code in both formulations
    warp = smcore._release_barriers(warp, trace["n_instr"], t)
    issued_any = jnp.zeros((), jnp.bool_)
    for sc in range(cfg.n_subcores):
        warp, sm, req, stats, did = _issue_subcore(
            warp, sm, req, stats, trace, t, sc, cfg, dyn)
        issued_any = issued_any | did
    stats = dict(
        stats,
        cycles_issue=stats["cycles_issue"] + jnp.where(issued_any, 1, 0),
        warp_cycles=stats["warp_cycles"]
        + jnp.sum(warp["active"], dtype=jnp.int32),
    )
    return warp, sm, req, stats


# --- seeded random SM states ------------------------------------------------

# 16 warp slots over 4 sub-cores, 8 MSHR rows, a 4 x 4 L1, a 16-slot
# address set, and 48 memory blocks, so that addresses meet in the L1
# and collide in the address set
CFG = GPUConfig(n_sm=8, warps_per_sm=16, n_subcores=4, l1_sets=4,
                l1_ways=4, mshr_per_sm=8, addrset_cap=16, mem_blocks=48,
                l2_slices=2, l2_sets=4, l2_ways=2, dram_channels=2)
N_SM = 24          # random SMs per batch
T = 40             # the cycle simulated first

SCENARIOS = ("mshr_full", "mshr_one_free", "l1_hit", "l1_evict",
             "addrset_overflow", "barriers", "ragged")


def make_trace(rng, scenario):
    n_instr = 12
    ops = rng.choice(N_CLASSES, size=n_instr,
                     p=[.15, .1, .1, .05, .3, .2, .1]).astype(np.int32)
    if scenario == "barriers":
        ops[::3] = BAR
    mode = rng.choice([A_STREAM, A_STRIDED, A_RANDOM], size=n_instr)
    trace = {
        "ops": ops, "dep": rng.random(n_instr) < 0.5,
        "addr_mode": mode.astype(np.int32),
        "addr_param": rng.integers(0, 5, n_instr).astype(np.int32),
        "n_ctas": np.int32(8), "warps_per_cta": np.int32(4),
        "n_instr": np.int32(n_instr),
    }
    if scenario == "ragged":
        # this kernel's instructions sit at an offset inside a flat array
        # shared with other kernels (core/batch.py:concat_kernels)
        base, total = 5, n_instr + 9
        for f, fill in (("ops", FP32), ("dep", True), ("addr_mode", 0),
                        ("addr_param", 0)):
            flat = np.full(total, fill, trace[f].dtype)
            flat[:base] = rng.permutation(np.resize(trace[f], base))
            flat[base:base + n_instr] = trace[f]
            trace[f] = flat
        trace["instr_base"] = np.int32(base)
    return {k: jnp.asarray(v) for k, v in trace.items()}


def make_state(rng, scenario):
    """A batch of N_SM random states of one SM each, as the SM phase
    sees them (leading SM axis)."""
    n, w, m = N_SM, CFG.warps_per_sm, CFG.mshr_per_sm
    sets, ways, cap = CFG.l1_sets, CFG.l1_ways, CFG.addrset_cap
    i32 = np.int32
    warp = {
        "pc": rng.integers(0, 14, (n, w)).astype(i32),
        "active": rng.random((n, w)) < 0.85,
        "ready_at": rng.integers(T - 4, T + 3, (n, w)).astype(i32),
        "pending": rng.integers(0, 3, (n, w)).astype(i32),
        "wait_mem": rng.random((n, w)) < 0.3,
        "wait_bar": rng.random((n, w)) < (0.4 if scenario == "barriers"
                                          else 0.05),
        "cta": rng.integers(-1, 4, (n, w)).astype(i32),
        "wic": rng.integers(0, 4, (n, w)).astype(i32),
    }
    if scenario == "barriers":
        # whole CTAs of 4 slots, some of them all at the barrier
        warp["cta"] = np.repeat(rng.integers(0, 8, (n, w // 4)), 4,
                                axis=1).astype(i32)
        warp["active"][:] = True
        warp["wait_bar"] |= rng.random((n, 1)) < 0.5
    # L1: the blocks of set s are s, s + sets, ... ; in "l1_hit" most ways
    # are filled
    blocks = np.arange(sets)[None, :, None] \
        + sets * rng.integers(0, CFG.mem_blocks // sets, (n, sets, ways))
    fill = 0.9 if scenario == "l1_hit" else 0.5
    l1_tag = np.where(rng.random((n, sets, ways)) < fill, blocks, -1)
    if scenario == "l1_evict":
        # every way of a set holds a distinct block, none of them one the
        # trace asks for, so that every miss evicts
        l1_tag = np.arange(sets)[None, :, None] + sets * (
            CFG.mem_blocks // sets + np.arange(ways)[None, None, :]
            + ways * np.arange(n)[:, None, None])
    aset_fill = 0.95 if scenario == "addrset_overflow" else 0.4
    aset = np.where(rng.random((n, cap)) < aset_fill,
                    rng.integers(0, 4 * CFG.mem_blocks, (n, cap)), -1)
    sm = {
        "last_issued": rng.integers(-1, w, (n, CFG.n_subcores)).astype(i32),
        "unit_free": rng.integers(T - 3, T + 2,
                                  (n, CFG.n_subcores, N_UNITS)).astype(i32),
        "l1_tag": l1_tag.astype(i32),
        "l1_lru": rng.integers(0, T, (n, sets, ways)).astype(i32),
        "addrset": aset.astype(i32),
        "addrset_over": rng.integers(0, 3, n).astype(i32),
    }
    stage = rng.integers(0, 4, (n, m))
    due = rng.integers(T - 3, T + 6, (n, m))
    if scenario == "mshr_full":
        # rows in flight, none due this cycle
        stage = rng.integers(1, 4, (n, m))
        due = np.where(stage == 3, T + 5, due)
    elif scenario == "mshr_one_free":
        stage = rng.integers(1, 4, (n, m))
        due = np.where(stage == 3, T + 5, due)
        stage[np.arange(n), rng.integers(0, m, n)] = 0
    req = {
        "stage": stage.astype(i32),
        "addr": rng.integers(0, CFG.mem_blocks, (n, m)).astype(i32),
        "t": due.astype(i32), "warp": rng.integers(0, w, (n, m)).astype(i32),
        "is_store": rng.random((n, m)) < 0.3,
    }
    stats = {k: rng.integers(0, 50, n).astype(i32) for k in (
        "issued", "issued_mem", "l1_hit", "l1_miss", "cycles_issue",
        "stall", "warp_cycles")}
    return jax.tree_util.tree_map(
        jnp.asarray, {"warp": warp, "sm": sm, "req": req, "stats": stats})


def make_dyn(rng, sched):
    scfg, dyn = split_config(CFG)
    flat = dict(dyn.flat())
    flat["sched"] = jnp.int32(sched)
    flat["lat"] = jnp.asarray(rng.integers(0, 20, N_CLASSES), jnp.int32)
    flat["disp"] = jnp.asarray(rng.integers(1, 5, N_CLASSES), jnp.int32)
    return split_config(scfg, flat)


def run_cycles(cycle, state, trace, scfg, dyn, n_cycles):
    """``n_cycles`` cycles from T, vmapped over the SMs; the state after
    each one."""
    @jax.jit
    def step(s, t):
        w, sm, r, st = jax.vmap(
            lambda w, sm, r, st: cycle(w, sm, r, st, trace, t, scfg, dyn))(
            s["warp"], s["sm"], s["req"], s["stats"])
        return {"warp": w, "sm": sm, "req": r, "stats": st}

    out = []
    for i in range(n_cycles):
        state = step(state, jnp.int32(T + i))
        out.append(jax.device_get(state))
    return out


def covered(scenario, before, after):
    """Whether the oracle's first cycle reached what ``scenario`` covers."""
    issued_mem = after["stats"]["issued_mem"] - before["stats"]["issued_mem"]
    hits = after["stats"]["l1_hit"] - before["stats"]["l1_hit"]
    misses = after["stats"]["l1_miss"] - before["stats"]["l1_miss"]
    free = np.sum(np.asarray(before["req"]["stage"]) == 0, axis=1)
    if scenario == "mshr_full":
        # no free row: memory ops are held back while others issue
        return (free == 0).all() and issued_mem.sum() == 0 and \
            (after["stats"]["issued"] > before["stats"]["issued"]).any()
    if scenario == "mshr_one_free":
        return (free == 1).all() and (misses == 1).any()
    if scenario == "l1_hit":
        return hits.sum() > 0 and misses.sum() > 0
    if scenario == "l1_evict":
        changed = np.asarray(after["sm"]["l1_tag"]) \
            != np.asarray(before["sm"]["l1_tag"])
        return misses.sum() > 0 and bool(
            (np.asarray(before["sm"]["l1_tag"])[changed] >= 0).all()) \
            and changed.any()
    if scenario == "addrset_overflow":
        return (after["sm"]["addrset_over"]
                > before["sm"]["addrset_over"]).any() and (
            np.asarray(after["sm"]["addrset"])
            != np.asarray(before["sm"]["addrset"])).any()
    if scenario == "barriers":
        return (np.asarray(before["warp"]["wait_bar"])
                & ~np.asarray(after["warp"]["wait_bar"])).any()
    if scenario == "ragged":
        return after["stats"]["issued"].sum() > before["stats"]["issued"].sum()
    raise KeyError(scenario)


@pytest.mark.parametrize("sched", ["gto", "lrr"])
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_cycle_matches_scatter_formulation(scenario, sched):
    seed = SCENARIOS.index(scenario) * 2 + (sched == "lrr")
    rng = np.random.default_rng(seed)
    scfg, dyn = make_dyn(rng, {"gto": 0, "lrr": 1}[sched])
    trace = make_trace(rng, scenario)
    state = make_state(rng, scenario)
    want = run_cycles(oracle_cycle, state, trace, scfg, dyn, 4)
    got = run_cycles(smcore.sm_cycle_single, state, trace, scfg, dyn, 4)
    for i, (w, g) in enumerate(zip(want, got)):
        for part in w:
            for field in w[part]:
                np.testing.assert_array_equal(
                    g[part][field], w[part][field],
                    err_msg=f"cycle {i}: {part}.{field}")
    assert covered(scenario, jax.device_get(state), want[0]), scenario
