"""The memory phase served in chunks of due rows, against the sorted,
scanned and scattered formulation it replaced, field for field.

``sim/memsys.py:mem_phase`` counts each due row's canonical position,
then serves K = ``chunk_rows`` positions a trip through one-hot masks,
with the queue recurrence in closed form.  The oracle below is the
earlier formulation, kept verbatim as a reference: a two-pass stable
``argsort`` per stage, segmented max-plus ``associative_scan``s, and
gathers and scatters over the whole request table.  Both run one
quantum on seeded request tables, and every field of the request,
memory and statistics state must agree.  Each case also checks that its
table reached what it is there to cover (the trip count, a DRAM row hit
across a chunk boundary, a hit in a later chunk on a set that an
earlier chunk's miss fills, ...), so a generator that drifts cannot
pass vacuously.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.sim import memsys
from repro.sim.config import RTX3080TI, GPUConfig, split_config
from repro.sim.state import init_state

BIG = jnp.int32(1 << 30)


# --- the oracle: the sort-and-scan formulation ------------------------------

def _seg_maxplus(seg_start, service, arrival):
    """finish_i = max(arrival_i, finish_{i-1}) + service_i, reset at segment
    starts.  All inputs sorted by segment; seg_start: bool (first of seg)."""
    a = service.astype(jnp.int32)
    b = (arrival + service).astype(jnp.int32)

    def comb(x, y):
        f1, a1, b1 = x
        f2, a2, b2 = y
        a = jnp.where(f2, a2, a1 + a2)
        b = jnp.where(f2, b2, jnp.maximum(b1 + a2, b2))
        return (f1 | f2, a, b)

    _, _, finish = jax.lax.associative_scan(comb, (seg_start, a, b))
    return finish.astype(jnp.int32)


def _lex_sort(primary, secondary, tertiary, valid):
    """argsort by (primary, secondary, tertiary), invalid rows last."""
    r = tertiary.shape[0]
    k2 = secondary * r + tertiary
    k2 = jnp.where(valid, k2, BIG)
    o1 = jnp.argsort(k2, stable=True)
    p = jnp.where(valid, primary, BIG)[o1]
    o2 = jnp.argsort(p, stable=True)
    return o1[o2]


def oracle_mem_phase(req, mem, stats, t0, cfg, dyn, sm_ids=None):
    horizon = t0 + cfg.quantum
    ns, m = req["stage"].shape
    r = ns * m
    stage = req["stage"].reshape(r)
    addr = req["addr"].reshape(r)
    t = req["t"].reshape(r)
    if sm_ids is None:
        sm_ids = jnp.arange(ns, dtype=jnp.int32)
    rid = (sm_ids[:, None] * m
           + jnp.arange(m, dtype=jnp.int32)[None, :]).reshape(r)

    # ---------------- stage 1: arrival at L2 slices -------------------------
    sel1 = (stage == 1) & (t < horizon)
    slc = addr % cfg.l2_slices
    order = _lex_sort(slc, t - t0, rid, sel1)
    o_sel = sel1[order]
    o_slc = jnp.where(o_sel, slc[order], cfg.l2_slices)
    o_t = t[order]
    o_addr = addr[order]
    o_rid = order.astype(jnp.int32)

    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), o_slc[1:] != o_slc[:-1]])
    arrival = jnp.maximum(o_t, mem["l2_busy"][jnp.clip(o_slc, 0,
                                                       cfg.l2_slices - 1)])
    service = jnp.ones((r,), jnp.int32)          # 1 request / cycle / slice
    finish = _seg_maxplus(seg_start, service, arrival)
    start = finish - service

    # L2 tag lookup (snapshot at quantum start)
    l2_set = (o_addr // cfg.l2_slices) % cfg.l2_sets
    slc_c = jnp.clip(o_slc, 0, cfg.l2_slices - 1)
    ways = mem["l2_tag"][slc_c, l2_set]          # (r, ways)
    hit = jnp.any(ways == o_addr[:, None], axis=1) & o_sel
    miss = o_sel & ~hit

    resp_t = start + dyn.cache.l2_lat + dyn.icnt.icnt_lat
    dram_t = start + dyn.cache.l2_lat + dyn.mem.part_lat

    new_stage = jnp.where(hit, 3, jnp.where(miss, 2, stage[order]))
    new_t = jnp.where(hit, resp_t, jnp.where(miss, dram_t, o_t))
    # scatter back (order is a permutation — unique indices)
    stage = stage.at[o_rid].set(new_stage)
    t = t.at[o_rid].set(new_t)

    # busy_until per slice: max finish (commutative -> safe scatter-max)
    l2_busy = mem["l2_busy"].at[slc_c].max(jnp.where(o_sel, finish, 0))

    # LRU touch on hits (monotone time -> scatter-max is exact)
    hway = jnp.argmax(ways == o_addr[:, None], axis=1)
    l2_lru = mem["l2_lru"].at[slc_c, l2_set, hway].max(
        jnp.where(hit, t0, -1))
    # insert on miss: victim = LRU way (snapshot); same-(slice,set) conflicts
    # resolved "last in canonical order wins": scatter-max the canonical
    # rank, then only the winning entry writes its tag (unique indices).
    victim = jnp.argmin(l2_lru[slc_c, l2_set], axis=1)
    rank = jnp.arange(r, dtype=jnp.int32)
    rank_grid = jnp.full(mem["l2_tag"].shape, -1, jnp.int32)
    rank_grid = rank_grid.at[slc_c, l2_set, victim].max(
        jnp.where(miss, rank, -1))
    win = miss & (rank_grid[slc_c, l2_set, victim] == rank)
    vway = jnp.where(win, victim, cfg.l2_ways)     # OOB → dropped
    l2_tag = mem["l2_tag"].at[slc_c, l2_set, vway].set(o_addr, mode="drop")
    l2_lru = l2_lru.at[slc_c, l2_set, vway].set(t0, mode="drop")

    stats = dict(stats,
                 l2_hit=stats["l2_hit"] + jnp.sum(hit, dtype=jnp.int32),
                 l2_miss=stats["l2_miss"] + jnp.sum(miss, dtype=jnp.int32))

    # ---------------- stage 2: DRAM channels --------------------------------
    sel2 = (stage == 2) & (t < horizon)
    ch = (addr % cfg.l2_slices) * cfg.dram_channels // cfg.l2_slices
    order2 = _lex_sort(ch, t - t0, rid, sel2)
    o_sel2 = sel2[order2]
    o_ch = jnp.where(o_sel2, ch[order2], cfg.dram_channels)
    o_t2 = t[order2]
    o_row = (addr[order2] // cfg.dram_row_div)
    o_rid2 = order2.astype(jnp.int32)
    ch_c = jnp.clip(o_ch, 0, cfg.dram_channels - 1)

    seg2 = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), o_ch[1:] != o_ch[:-1]])
    prev_row = jnp.concatenate([jnp.full((1,), -2, jnp.int32), o_row[:-1]])
    prev_row = jnp.where(seg2, mem["dram_row"][ch_c], prev_row)
    row_hit = (o_row == prev_row) & o_sel2
    service2 = jnp.where(row_hit, dyn.mem.dram_burst,
                         dyn.mem.dram_burst + dyn.mem.dram_row_penalty)
    arrival2 = jnp.maximum(o_t2, mem["dram_busy"][ch_c])
    finish2 = _seg_maxplus(seg2, service2, arrival2)

    resp2 = finish2 + dyn.mem.part_lat + dyn.icnt.icnt_lat
    stage = stage.at[o_rid2].set(jnp.where(o_sel2, 3, stage[o_rid2]))
    t = t.at[o_rid2].set(jnp.where(o_sel2, resp2, t[o_rid2]))

    dram_busy = mem["dram_busy"].at[ch_c].max(jnp.where(o_sel2, finish2, 0))
    seg_last = jnp.concatenate([o_ch[1:] != o_ch[:-1],
                                jnp.ones((1,), jnp.bool_)])
    # open row per channel = row of the channel's last served request; only
    # that row writes (unique indices), every other row goes out of range
    # and is dropped, as in the L2 insert above
    last_sel = seg_last & o_sel2
    dram_row = mem["dram_row"].at[jnp.where(last_sel, ch_c,
                                            cfg.dram_channels)].set(
        o_row, mode="drop")

    stats = dict(stats,
                 dram_req=stats["dram_req"] + jnp.sum(o_sel2,
                                                      dtype=jnp.int32),
                 dram_row_hit=stats["dram_row_hit"]
                 + jnp.sum(row_hit, dtype=jnp.int32))

    req = dict(req, stage=stage.reshape(ns, m), t=t.reshape(ns, m))
    mem = dict(mem, l2_tag=l2_tag, l2_lru=l2_lru, l2_busy=l2_busy,
               dram_busy=dram_busy, dram_row=dram_row)
    return req, mem, stats


# --- seeded request tables --------------------------------------------------

# 16 SMs x 48 MSHR rows = 768 rows, so K = 128 and 2K + 1 due rows fit
# at each stage
MID = GPUConfig(n_sm=16, warps_per_sm=8, n_subcores=2, l1_sets=16,
                l1_ways=4, l2_slices=8, l2_sets=16, l2_ways=4,
                dram_channels=4, mshr_per_sm=48, addrset_cap=256)
T0 = 4096


def table(cfg, rng, n1, n2, t0=T0, pool=None, extra=True):
    """A request table with exactly ``n1`` rows due at L2 and ``n2`` due at
    DRAM at quantum start t0, addresses drawn from ``pool`` (repeats make
    L2 hits, shared sets and DRAM row hits), plus rows in flight that are
    not due; and an L2 holding half the pool, with LRU stamps, busy times
    and open rows around t0."""
    scfg, _ = split_config(cfg)
    state = init_state(scfg)
    ns, m = scfg.n_sm, scfg.mshr_per_sm
    r, q = ns * m, scfg.quantum
    if pool is None:
        pool = rng.integers(0, 4 * scfg.l2_slices * scfg.l2_sets, 96)
    rows = rng.permutation(r)
    stage = np.zeros(r, np.int32)
    t = np.zeros(r, np.int32)
    addr = rng.choice(pool, r).astype(np.int32)
    stage[rows[:n1]] = 1
    stage[rows[n1:n1 + n2]] = 2
    t[rows[:n1 + n2]] = t0 + rng.integers(0, q, n1 + n2)
    if extra:                   # in flight, not due: later events, or done
        rest = rows[n1 + n2:]
        kind = rng.integers(0, 4, rest.size)
        stage[rest] = kind
        t[rest] = np.where(kind == 3, t0 + rng.integers(-q, 4 * q, rest.size),
                           t0 + q + rng.integers(0, 4 * q, rest.size))
    req = dict(state["req"], stage=jnp.asarray(stage.reshape(ns, m)),
               t=jnp.asarray(t.reshape(ns, m)),
               addr=jnp.asarray(addr.reshape(ns, m)))
    shape = (scfg.l2_slices, scfg.l2_sets, scfg.l2_ways)
    tag = np.full(shape, -1, np.int32)
    for a in rng.choice(pool, pool.size // 2, replace=False):
        s, st_ = a % scfg.l2_slices, (a // scfg.l2_slices) % scfg.l2_sets
        if a not in tag[s, st_]:
            tag[s, st_, rng.integers(0, scfg.l2_ways)] = a
    mem = dict(
        state["mem"], l2_tag=jnp.asarray(tag),
        l2_lru=jnp.asarray(rng.integers(0, t0, shape), jnp.int32),
        l2_busy=jnp.asarray(t0 + rng.integers(-8, 24, scfg.l2_slices),
                            jnp.int32),
        dram_busy=jnp.asarray(t0 + rng.integers(-8, 24, scfg.dram_channels),
                              jnp.int32),
        dram_row=jnp.asarray(rng.integers(-1, int(pool.max())
                                          // scfg.dram_row_div + 1,
                                          scfg.dram_channels), jnp.int32))
    stats = {k: jnp.asarray(rng.integers(0, 1000), jnp.int32)
             for k in state["stats"]}
    return req, mem, stats


def run_both(cfg, req, mem, stats, t0=T0, sm_ids=None, dyn=None):
    """Both formulations on the same inputs; asserts every field equal and
    returns the outputs as numpy."""
    scfg, dyn0 = split_config(cfg)
    dyn = dyn0 if dyn is None else dyn
    args = (req, mem, stats, jnp.int32(t0))
    got = jax.jit(lambda q, m_, s, t: memsys.mem_phase(
        q, m_, s, t, scfg, dyn, sm_ids=sm_ids))(*args)
    want = jax.jit(lambda q, m_, s, t: oracle_mem_phase(
        q, m_, s, t, scfg, dyn, sm_ids=sm_ids))(*args)
    got, want = jax.tree_util.tree_map(np.asarray, (got, want))
    for part, g, w in zip(("req", "mem", "stats"), got, want):
        assert set(g) == set(w), part
        for name in w:
            assert np.array_equal(g[name], w[name]), f"{part}.{name}"
    return got


def due_counts(cfg, req, t0):
    """Rows due at L2 at quantum start (stage 1, t < t0 + Δ), and at DRAM."""
    stage, t = np.asarray(req["stage"]), np.asarray(req["t"])
    due = t < t0 + cfg.quantum
    return int(((stage == 1) & due).sum()), int(((stage == 2) & due).sum())


def canonical(cfg, req, t0, which, sm_ids=None):
    """Row indices of the due rows of stage ``which`` (1: L2, 2: DRAM) in
    canonical order (slice or channel, t - t0, row id)."""
    ns, m = req["stage"].shape
    stage = np.asarray(req["stage"]).ravel()
    t = np.asarray(req["t"]).ravel()
    addr = np.asarray(req["addr"]).ravel()
    ids = np.arange(ns) if sm_ids is None else np.asarray(sm_ids)
    rid = (ids[:, None] * m + np.arange(m)[None, :]).ravel()
    seg = addr % cfg.l2_slices
    if which == 2:
        seg = seg * cfg.dram_channels // cfg.l2_slices
    rows = np.nonzero((stage == which) & (t < t0 + cfg.quantum))[0]
    return rows[np.lexsort((rid[rows], t[rows] - t0, seg[rows]))]


# --- the cases ----------------------------------------------------------------

K = memsys.chunk_rows(split_config(MID)[0])


@pytest.mark.parametrize("n_due", [0, 1, K - 1, K, K + 1, 2 * K + 1])
def test_due_counts_across_chunk_sizes(n_due):
    rng = np.random.default_rng(n_due)
    req, mem, stats = table(MID, rng, n_due, n_due)
    assert K == 128 and MID.n_sm * MID.mshr_per_sm == 768
    assert due_counts(MID, req, T0) == (n_due, n_due)
    out = run_both(MID, req, mem, stats)
    # the L2 lookups found both outcomes, every due row was served once
    d_hit = out[2]["l2_hit"] - np.asarray(stats["l2_hit"])
    d_miss = out[2]["l2_miss"] - np.asarray(stats["l2_miss"])
    assert d_hit + d_miss == n_due
    assert out[2]["dram_req"] - np.asarray(stats["dram_req"]) == n_due
    if n_due > K:
        assert d_hit > 0 and d_miss > 0
    assert memsys.n_trips(n_due, split_config(MID)[0]) == -(-n_due // K)


@pytest.mark.parametrize("stage", [1, 2])
def test_whole_table_due_at_one_resource(stage):
    """All 2,560 rows of the 80-SM model due at one L2 slice (one DRAM
    channel): twenty trips, one resource's queue running through all."""
    cfg = RTX3080TI
    scfg, _ = split_config(cfg)
    rng = np.random.default_rng(17 + stage)
    r = scfg.n_sm * scfg.mshr_per_sm
    # every address on slice 5 (and so on DRAM channel 5 * 24 // 48)
    pool = 5 + scfg.l2_slices * rng.integers(0, 4096, 300)
    req, mem, stats = table(cfg, rng, r if stage == 1 else 0,
                            r if stage == 2 else 0, pool=pool, extra=False)
    assert memsys.n_trips(r, scfg) == 20
    out = run_both(cfg, req, mem, stats)
    assert (out[0]["stage"] >= 2).all()
    if stage == 2:
        assert (out[0]["stage"] == 3).all()
        assert out[2]["dram_row_hit"] > np.asarray(stats["dram_row_hit"])


def test_ties_across_relabeled_sms():
    """Many due rows share (slice, t - t0); the tie-break is the row id
    over ORIGINAL SM ids, so a relabeled SM axis changes nothing."""
    rng = np.random.default_rng(3)
    req, mem, stats = table(MID, rng, 2 * K + 1, K + 7,
                            pool=rng.integers(0, 3 * MID.l2_slices, 12))
    req = dict(req, t=jnp.where(req["t"] < T0 + MID.quantum,
                                T0 + (req["t"] % 2), req["t"]))
    sm_ids = jnp.asarray(rng.permutation(MID.n_sm), jnp.int32)
    order = canonical(MID, req, T0, 1, sm_ids)
    t = np.asarray(req["t"]).ravel()[order]
    a = np.asarray(req["addr"]).ravel()[order] % MID.l2_slices
    ties = (a[1:] == a[:-1]) & (t[1:] == t[:-1])
    assert ties.sum() > K                       # most rows tie on (slice, t)
    assert order.size == 2 * K + 1
    run_both(MID, req, mem, stats, sm_ids=sm_ids)


def test_dram_row_hit_across_chunk_boundary():
    """K + 5 rows due at one DRAM channel, all on one row: the row at
    position K is a row hit because of the open row the first trip
    leaves on the channel."""
    rng = np.random.default_rng(5)
    pool = np.asarray([2 + MID.l2_slices * j for j in range(3)])
    req, mem, stats = table(MID, rng, 0, K + 5, pool=pool, extra=False)
    mem = dict(mem, dram_row=jnp.full_like(mem["dram_row"], 99))
    order = canonical(MID, req, T0, 2)
    rows = np.asarray(req["addr"]).ravel()[order] // MID.dram_row_div
    assert order.size == K + 5 and (rows[K - 1] == rows[K])
    out = run_both(MID, req, mem, stats)
    # every row after the first is a hit, the one at position K included
    assert out[2]["dram_row_hit"] - np.asarray(stats["dram_row_hit"]) \
        == K + 4


def test_hit_in_later_chunk_on_a_set_an_earlier_miss_fills():
    """A set whose LRU way is hit in the second trip, and missed by a row
    of the first trip: the miss fills the LRU way as it stands after the
    later trip's touch, so filling inside the first trip would evict the
    way the hit just touched."""
    rng = np.random.default_rng(11)
    scfg, _ = split_config(MID)
    s, st_ = 3, 6
    hit_a = s + scfg.l2_slices * st_                     # resident
    miss_a = hit_a + scfg.l2_slices * scfg.l2_sets       # same set, absent
    req, mem, stats = table(MID, rng, K + 20, 0, extra=False)
    # K - 1 rows on slice 1 (positions 0 .. K-2), then the miss and the
    # hit on slice 3, ordered by their times (positions K-1 and K), then
    # rows on slice 5
    rows = canonical(MID, req, T0, 1)
    flat = np.array(req["addr"]).ravel()
    t = np.array(req["t"]).ravel()
    flat[rows[:K - 1]] = 1 + scfg.l2_slices * np.arange(K - 1)
    flat[rows[K + 1:]] = 5 + scfg.l2_slices * np.arange(19)
    flat[rows[K - 1]], t[rows[K - 1]] = miss_a, T0
    flat[rows[K]], t[rows[K]] = hit_a, T0 + scfg.quantum - 1
    req = dict(req, addr=jnp.asarray(flat.reshape(req["addr"].shape)),
               t=jnp.asarray(t.reshape(req["t"].shape)))
    tag = np.full((scfg.l2_slices, scfg.l2_sets, scfg.l2_ways), -1, np.int32)
    tag[s, st_] = [hit_a, 900, 901, 902]
    lru = np.full(tag.shape, 10, np.int32)
    lru[s, st_] = [1, 5, 7, 9]                   # way 0, the hit's, is LRU
    mem = dict(mem, l2_tag=jnp.asarray(tag), l2_lru=jnp.asarray(lru))
    order = canonical(MID, req, T0, 1)
    assert list(flat[order[[K - 1, K]]]) == [miss_a, hit_a]
    assert (tag == miss_a).sum() == 0
    out = run_both(MID, req, mem, stats)
    # the hit kept its way; the miss took way 1, the LRU after the touch
    assert list(out[1]["l2_tag"][s, st_]) == [hit_a, miss_a, 901, 902]


def test_time_shift_past_int32_overflow_across_chunks():
    """Multi-trip case at t0 = 2^25 - 8, where a packed key holding the
    absolute time would overflow int32."""
    t0 = (1 << 25) - 8
    rng = np.random.default_rng(25)
    req, mem, stats = table(MID, rng, 2 * K + 1, 2 * K + 1, t0=t0)
    assert due_counts(MID, req, t0) == (2 * K + 1, 2 * K + 1)
    run_both(MID, req, mem, stats, t0=t0)


def test_miss_due_at_dram_in_the_same_quantum():
    """With short L2 and partition latencies a stage-1 miss falls due at
    DRAM in the quantum it missed, and the DRAM stage serves it."""
    rng = np.random.default_rng(8)
    cfg = dataclasses.replace(MID, l2_lat=0, part_lat=0)
    req, mem, stats = table(cfg, rng, K + 40, 30)
    out = run_both(cfg, req, mem, stats)
    served = out[2]["dram_req"] - np.asarray(stats["dram_req"])
    assert served > 30


def test_vmapped_lanes_with_different_trip_counts():
    """Two lanes, one with 1 due row a stage and one with 2K + 1: the
    loop runs three trips for both, and each lane equals its solo run."""
    scfg, dyn = split_config(MID)
    rng = np.random.default_rng(2)
    lanes = [table(MID, rng, n, n) for n in (1, 2 * K + 1)]
    assert [memsys.n_trips(due_counts(MID, q, T0)[0], scfg)
            for q, _, _ in lanes] == [1, 3]
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *lanes)
    got = jax.jit(jax.vmap(lambda q, m_, s: memsys.mem_phase(
        q, m_, s, jnp.int32(T0), scfg, dyn)))(*stacked)
    for i, lane in enumerate(lanes):
        solo = run_both(MID, *lane)
        mine = jax.tree_util.tree_map(lambda x: np.asarray(x[i]), got)
        for a, b in zip(jax.tree_util.tree_leaves(mine),
                        jax.tree_util.tree_leaves(solo)):
            assert np.array_equal(a, b)


# --- the K x K helpers against a plain loop ---------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 50),
                          st.integers(1, 5)), min_size=1, max_size=40),
       st.integers(0, 8))
def test_chunk_finish_matches_loop(items, n_invalid):
    """finish_i = max(arrival_i, finish_{i-1}) + service_i per segment,
    over the valid prefix of the slots."""
    items.sort(key=lambda x: x[0])
    pad = [(0, 0, 1)] * n_invalid
    seg, arr, srv = (np.array([x[i] for x in items + pad], np.int32)
                     for i in range(3))
    valid = np.arange(seg.size) < len(items)
    same = memsys._earlier_same(jnp.asarray(seg), jnp.asarray(valid))
    got = np.asarray(memsys._chunk_finish(same, jnp.asarray(arr),
                                          jnp.asarray(srv)))
    finish, want = {}, []
    for s, a, v in items:
        f = max(a, finish.get(s, 0)) + v
        finish[s] = f
        want.append(f)
    assert (got[:len(items)] == np.array(want)).all()


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(-3, 19),
                          st.booleans()), min_size=1, max_size=40),
       st.integers(0, 1 << 16))
def test_positions_match_lexsort(items, seed):
    """A due row's position is its rank in (seg, clamped dt, rid) order;
    rows not due get NOT_DUE."""
    n = len(items)
    seg = np.array([x[0] for x in items], np.int32)
    dt = np.array([x[1] for x in items], np.int32)
    due = np.array([x[2] for x in items])
    rid = np.random.default_rng(seed).permutation(n).astype(np.int32)
    got = np.asarray(memsys._positions(jnp.asarray(seg), jnp.asarray(dt),
                                       jnp.asarray(rid), jnp.asarray(due),
                                       4, 16))
    rows = np.nonzero(due)[0]
    order = rows[np.lexsort((rid[rows], np.clip(dt[rows], 0, 15),
                             seg[rows]))]
    want = np.full(n, memsys.NOT_DUE, np.int64)
    want[order] = np.arange(order.size)
    assert (got == want).all()
