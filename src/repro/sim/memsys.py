"""Memory-system phase: interconnect → L2 slices → DRAM channels.

Runs once per machine quantum (Δ cycles) over the *full* request table —
this is Algorithm 1's serial region (lines 8–19).  Under the sharded
execution mode every device computes it replicated from an all-gathered
table, which preserves the sequential semantics bit-exactly.

Queueing at L2 slices and DRAM channels is an exact M/D/1-style recurrence
  finish_i = max(arrival_i, finish_{i-1}) + service_i
evaluated with a *segmented max-plus associative scan* over requests sorted
by (resource, event-time, row-id) — fully deterministic, independent of the
number of devices and of the window size (the recurrence carries
``busy_until`` across quanta).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sim.config import DynConfig, StaticConfig

BIG = jnp.int32(1 << 30)


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
    """argsort by (primary, secondary, tertiary), invalid rows last.
    int32-safe two-pass stable lexsort (no x64 in this environment):
    secondary and tertiary (< 2^12 rows) pack into one key; a second
    stable pass orders by primary.

    ``secondary`` must be SMALL — callers pass the *quantum-relative*
    event time ``t - t0`` (every valid row satisfies t0 ≤ t < t0 + Δ, so
    it lies in [0, Δ)), never the absolute cycle: an absolute time (up to
    2^20+ cycles) times the row count overflows the packed int32 key on
    long runs and silently scrambles the service order
    (tests/test_memsys.py:test_mem_phase_time_shift_invariance)."""
    r = tertiary.shape[0]
    k2 = secondary * r + tertiary
    k2 = jnp.where(valid, k2, BIG)
    o1 = jnp.argsort(k2, stable=True)
    p = jnp.where(valid, primary, BIG)[o1]
    o2 = jnp.argsort(p, stable=True)
    return o1[o2]


def mem_phase(req: dict, mem: dict, stats: dict, t0, cfg: StaticConfig,
              dyn: DynConfig, sm_ids=None):
    """Process the event horizon [t0, t0+Δ). Returns (req, mem, stats).

    cfg is the hashable static shape config; dyn is the typed DynConfig of
    traced timing parameters (dyn.cache.l2_lat, dyn.mem.part_lat /
    dram_burst / dram_row_penalty, dyn.icnt.icnt_lat) so a vmapped config
    sweep varies them per lane.

    sm_ids: (n_sm,) ORIGINAL SM id per array position — canonical tie-break
    order must follow original ids so results are invariant under SM-axis
    relabeling (the 'dynamic' device-assignment policy)."""
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
