"""Memory-system phase: interconnect → L2 slices → DRAM channels.

Runs once per machine quantum (Δ cycles) over the request table — this is
Algorithm 1's serial region (lines 8–19).  Under the sharded execution
mode every device computes it replicated from an all-gathered table,
which preserves the sequential semantics bit-exactly.

Queueing at L2 slices and DRAM channels is an exact M/D/1-style recurrence
  finish_i = max(arrival_i, finish_{i-1}) + service_i
over each stage's *due* requests (event time before the quantum's
horizon) in the canonical order (resource, event time − t0, row id) —
fully deterministic, independent of the number of devices and of the
window size (the recurrence carries ``busy_until`` across quanta).

Only the due rows are served, ``chunk_rows`` (K) at a time:

  · each due row's canonical position is the number of due rows with a
    smaller packed key — one compare-and-sum over the table, no sort;
  · a ``while_loop`` makes one trip per K positions (none when nothing is
    due).  A trip moves its rows into K slots and back through a one-hot
    (table row × slot) mask, and serves them with K-row arithmetic: the
    recurrence in closed form over a K × K "same resource, earlier
    position" mask, the per-resource busy time and the open DRAM row
    carried from trip to trip (the recurrence is sequential, so chunking
    it is exact).

The request table is read and written by dense elementwise ops and
reductions alone: no sort, gather or scatter runs over it.  Under vmap
the loop runs as many trips as the lane with the most due rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sim.config import DynConfig, StaticConfig

BIG = jnp.int32(1 << 30)
NOT_DUE = (1 << 31) - 1         # packed key and position of a row not due
CHUNK = 128                     # due rows a trip serves, at most


def chunk_rows(cfg: StaticConfig) -> int:
    """K: the due rows one trip serves — 128, or the whole request table
    when it is smaller (then every quantum takes at most one trip)."""
    return min(CHUNK, cfg.n_sm * cfg.mshr_per_sm)


def n_trips(n_due, cfg: StaticConfig):
    """Trips a stage makes over ``n_due`` due rows: ceil(n_due / K), as
    the phase's loops run one while the position at a trip's base is
    due; ``trips_between`` counts with it."""
    k = chunk_rows(cfg)
    return (n_due + k - 1) // k


def trips_between(before: dict, after: dict, cfg: StaticConfig):
    """Trips both stages made between two statistics snapshots one
    quantum apart (core/telemetry.py counts them as ``mem_chunks``).  The
    phase adds each stage's due count to its statistics (l2_hit +
    l2_miss at the L2 slices, dram_req at the DRAM channels), so their
    gains over the quantum are its due counts."""
    def l2(s):
        return s["l2_hit"] + s["l2_miss"]

    return n_trips(l2(after) - l2(before), cfg) \
        + n_trips(after["dram_req"] - before["dram_req"], cfg)


def _positions(seg, dt, rid, due, n_seg: int, quantum: int):
    """Canonical position of each due row: the number of due rows whose
    packed key (seg, dt, rid) is smaller; ``NOT_DUE`` for the others.

    dt is the quantum-relative event time: a due row satisfies
    t0 ≤ t < t0 + Δ (requests enter at least icnt_lat ≥ Δ cycles after
    they issue), and the key clamps it to [0, Δ) besides.  An absolute
    time would overflow the int32 key on long runs
    (tests/test_memsys.py:
    test_mem_phase_time_shift_invariance_past_int32_overflow)."""
    r = rid.shape[0]
    assert n_seg * quantum * r < NOT_DUE, (n_seg, quantum, r)
    key = (seg * quantum + jnp.clip(dt, 0, quantum - 1)) * r + rid
    key = jnp.where(due, key, NOT_DUE)
    pos = jnp.sum(key[None, :] < key[:, None], axis=1, dtype=jnp.int32)
    return jnp.where(due, pos, NOT_DUE)


def _take(onehot, x):
    """Each slot's value of ``x`` (R,) through the (R, K) one-hot mask."""
    return jnp.sum(jnp.where(onehot, x[:, None], 0), axis=0, dtype=x.dtype)


def _put(onehot, inside, x, v):
    """Write each slot's value ``v`` (K,) back to its row of ``x`` (R,);
    ``inside`` marks the rows that hold a slot."""
    back = jnp.sum(jnp.where(onehot, v[None, :], 0), axis=1, dtype=x.dtype)
    return jnp.where(inside, back, x)


def _pick(at, table):
    """Each slot's entry of a per-resource ``table`` (N,), through the
    (K, N) mask of the slot's resource."""
    return jnp.sum(jnp.where(at, table[None, :], 0), axis=1,
                   dtype=table.dtype)


def _earlier_same(seg, valid):
    """(K, K) mask, [i, j]: slot j holds a due row of slot i's resource at
    the same or an earlier position."""
    i = jnp.arange(seg.shape[0])
    return (seg[:, None] == seg[None, :]) & (i[None, :] <= i[:, None]) \
        & valid[None, :]


def _chunk_finish(same, arrival, service):
    """finish_i = max(arrival_i, finish_{i-1}) + service_i along each
    resource's run of slots, in closed form: the max over slots j of
    i's run up to i of arrival_j + service_j + … + service_i.  Each
    resource's slots must be contiguous, as canonical order makes them."""
    i = jnp.arange(service.shape[0])
    csum = jnp.sum(jnp.where(i[None, :] <= i[:, None], service[None, :], 0),
                   axis=1, dtype=jnp.int32)
    cand = arrival[None, :] + service[None, :] + csum[:, None] \
        - csum[None, :]
    return jnp.max(jnp.where(same, cand, -BIG), axis=1)


def _busy_after(busy, at, valid, finish):
    """Per-resource busy-until after a trip: the latest finish of its
    rows in the trip (finishes only grow along a run)."""
    mine = at & valid[:, None]
    return jnp.maximum(busy, jnp.max(jnp.where(mine, finish[:, None], -BIG),
                                      axis=0))


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
    k = chunk_rows(cfg)
    stage = req["stage"].reshape(r)
    addr = req["addr"].reshape(r)
    t = req["t"].reshape(r)
    if sm_ids is None:
        sm_ids = jnp.arange(ns, dtype=jnp.int32)
    rid = (sm_ids[:, None] * m
           + jnp.arange(m, dtype=jnp.int32)[None, :]).reshape(r)
    slot = jnp.arange(k, dtype=jnp.int32)
    i32 = jnp.int32

    def chunk(pos, base, n_due):
        """The one-hot (row × slot) mask of the trip over positions
        [base, base + K), the rows it holds, and its valid slots
        (positions below n_due)."""
        onehot = pos[:, None] == base + slot[None, :]
        inside = (pos >= base) & (pos < base + k)
        return onehot, inside, base + slot < n_due

    # ---------------- stage 1: arrival at L2 slices -------------------------
    slices = jnp.arange(cfg.l2_slices, dtype=i32)
    due1 = (stage == 1) & (t < horizon)
    n1 = jnp.sum(due1, dtype=i32)
    pos1 = _positions(addr % cfg.l2_slices, t - t0, rid, due1,
                      cfg.l2_slices, cfg.quantum)
    tag0 = mem["l2_tag"]                  # lookups see the quantum's start

    def l2_trip(carry):
        base, stage, t, busy, lru, fill, n_hit = carry
        onehot, inside, valid = chunk(pos1, base, n1)
        tk, ak = _take(onehot, t), _take(onehot, addr)
        slc = ak % cfg.l2_slices
        l2_set = (ak // cfg.l2_slices) % cfg.l2_sets
        at = slc[:, None] == slices[None, :]
        arrival = jnp.maximum(tk, _pick(at, busy))
        # 1 request / cycle / slice
        finish = _chunk_finish(_earlier_same(slc, valid), arrival,
                               jnp.ones((k,), i32))
        start = finish - 1
        match = tag0[slc, l2_set] == ak[:, None]          # (K, ways)
        hit = jnp.any(match, axis=1) & valid
        miss = valid & ~hit
        resp_t = start + dyn.cache.l2_lat + dyn.icnt.icnt_lat
        dram_t = start + dyn.cache.l2_lat + dyn.mem.part_lat
        t = _put(onehot, inside, t, jnp.where(hit, resp_t, dram_t))
        stage = _put(onehot, inside, stage, jnp.where(hit, 3, 2))
        busy = _busy_after(busy, at, valid, finish)
        # LRU touch on hits (monotone time -> max is exact)
        lru = lru.at[jnp.where(hit, slc, cfg.l2_slices), l2_set,
                     jnp.argmax(match, axis=1)].max(t0, mode="drop")
        # each (slice, set) is filled by its last miss in canonical order:
        # here the trip's misses with no later miss on their set, and a
        # later trip's overwrite theirs (unique indices; others dropped)
        later = (slc[None, :] == slc[:, None]) \
            & (l2_set[None, :] == l2_set[:, None]) & miss[None, :] \
            & (slot[None, :] > slot[:, None])
        last = miss & ~jnp.any(later, axis=1)
        fill = fill.at[jnp.where(last, slc, cfg.l2_slices), l2_set].set(
            jnp.stack([jnp.ones_like(ak), ak], axis=1), mode="drop")
        n_hit = n_hit + jnp.sum(hit, dtype=i32)
        return base + k, stage, t, busy, lru, fill, n_hit

    zero = jnp.zeros((), i32)
    fill0 = jnp.zeros((cfg.l2_slices, cfg.l2_sets, 2), i32)
    # n_trips(n1) trips: one while the position at base is due
    _, stage, t, l2_busy, l2_lru, fill, n_hit = jax.lax.while_loop(
        lambda carry: carry[0] < n1, l2_trip,
        (zero, stage, t, mem["l2_busy"], mem["l2_lru"], fill0, zero))

    # insert on miss, after every hit's touch: the victim is the (slice,
    # set)'s LRU way as it stands now, the tag its last miss's address
    victim = jnp.argmin(l2_lru, axis=2)
    ins = (fill[..., :1] == 1) & (
        jnp.arange(cfg.l2_ways, dtype=i32) == victim[..., None])
    l2_tag = jnp.where(ins, fill[..., 1:], tag0)
    l2_lru = jnp.where(ins, t0, l2_lru)

    stats = dict(stats,
                 l2_hit=stats["l2_hit"] + n_hit,
                 l2_miss=stats["l2_miss"] + n1 - n_hit)

    # ---------------- stage 2: DRAM channels --------------------------------
    channels = jnp.arange(cfg.dram_channels, dtype=i32)
    due2 = (stage == 2) & (t < horizon)
    n2 = jnp.sum(due2, dtype=i32)
    pos2 = _positions(
        (addr % cfg.l2_slices) * cfg.dram_channels // cfg.l2_slices, t - t0,
        rid, due2, cfg.dram_channels, cfg.quantum)

    def dram_trip(carry):
        base, stage, t, busy, open_row, n_row_hit = carry
        onehot, inside, valid = chunk(pos2, base, n2)
        tk, ak = _take(onehot, t), _take(onehot, addr)
        ch = (ak % cfg.l2_slices) * cfg.dram_channels // cfg.l2_slices
        row = ak // cfg.dram_row_div
        at = ch[:, None] == channels[None, :]
        # the row before on the channel: the previous slot's when it
        # serves the same channel, else the channel's open row
        follows = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                                   (ch[1:] == ch[:-1]) & valid[:-1]])
        prev_row = jnp.where(follows, jnp.concatenate([row[:1], row[:-1]]),
                             _pick(at, open_row))
        row_hit = (row == prev_row) & valid
        service = jnp.where(row_hit, dyn.mem.dram_burst,
                            dyn.mem.dram_burst + dyn.mem.dram_row_penalty)
        arrival = jnp.maximum(tk, _pick(at, busy))
        finish = _chunk_finish(_earlier_same(ch, valid), arrival, service)
        t = _put(onehot, inside, t,
                 finish + dyn.mem.part_lat + dyn.icnt.icnt_lat)
        stage = jnp.where(inside, 3, stage)
        busy = _busy_after(busy, at, valid, finish)
        # open row per channel = row of the channel's last served request
        last = valid & ~jnp.concatenate([follows[1:] & valid[1:],
                                         jnp.zeros((1,), jnp.bool_)])
        at_last = at & last[:, None]
        open_row = jnp.where(jnp.any(at_last, axis=0),
                             _take(at_last, row), open_row)
        n_row_hit = n_row_hit + jnp.sum(row_hit, dtype=i32)
        return base + k, stage, t, busy, open_row, n_row_hit

    _, stage, t, dram_busy, dram_row, n_row_hit = jax.lax.while_loop(
        lambda carry: carry[0] < n2, dram_trip,
        (zero, stage, t, mem["dram_busy"], mem["dram_row"], zero))

    stats = dict(stats,
                 dram_req=stats["dram_req"] + n2,
                 dram_row_hit=stats["dram_row_hit"] + n_row_hit)

    req = dict(req, stage=stage.reshape(ns, m), t=t.reshape(ns, m))
    mem = dict(mem, l2_tag=l2_tag, l2_lru=l2_lru, l2_busy=l2_busy,
               dram_busy=dram_busy, dram_row=dram_row)
    return req, mem, stats
