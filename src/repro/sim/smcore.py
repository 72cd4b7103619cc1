"""SM phase — the parallel region (>93% of Accel-sim's runtime, Fig. 4).

``sm_quantum_single`` simulates ONE SM for Δ cycles touching only that SM's
state slice (warps, L1, its MSHR rows, its stats) — zero cross-SM data flow.
core/parallel.py runs it vectorized (vmap), serialized (lax.map — the
single-thread reference), or sharded (shard_map over the 'sm' mesh axis).

Per cycle: deliver resolved memory responses, release CTA barriers, and
on each sub-core pick an issuable warp (GTO: greedy-then-oldest; or LRR),
look up L1 on memory ops (miss ⇒ allocate an MSHR row that the memory
phase will service next quantum), update the scoreboard-lite dependency
state and the per-SM stats.

Config threading: every function takes the hashable ``StaticConfig`` (shape
decisions: array sizes, loop bounds, sub-core count) plus the typed
``DynConfig`` pytree of traced timing parameters — including the per-class
result-latency (``dyn.core.lat``) and dispatch-interval (``dyn.core.disp``)
tables, which are read as traced arrays here, never baked in as module
constants.  Nothing numeric is closed over as a Python constant, so the
whole SM phase vmaps over a batch of dynamic configs (core/sweep.py) —
per-class timing included.  Only the class→unit port mapping
(``UNIT_OF_CLASS``) stays static: it is structural, not a timing numeric.

Formulation: one-hot selects, no scatters.  Every read and write of
SM-local state at an index computed inside the cycle (the picked warp,
its MSHR row, its L1 set and way, its address-set slot, its unit, the
class tables) is a masked select or reduction over that state's own small
static axis: ``where(iota == i, new, old)`` to write, ``sum(where(iota ==
i, x, 0))`` to read.  Under vmap over the SMs a scalar-indexed ``x[i]`` or
``x.at[i].set`` becomes a batched gather or scatter, and on the TPU each
of those is a kernel of its own that XLA does not fuse with its
neighbours; a cycle written that way is a serial chain of such tiny ops
(12 warps a sub-core, 32 MSHR rows, 8 ways), and the chip spends its
time launching them.  The masked forms fuse into a few elementwise
kernels.

The one gather left is the instruction fetch from the trace tables
(``_fetch``), once per cycle for all warps: a one-hot over the trace
would grow with its length, a gather does not.  Fetching before the
first sub-core issues is exact, because sub-cores own disjoint warp slots
and an issue changes only its own warp's ``pc``.  For the same reason
every sub-core's scheduler pick is made at once (``_select``), for both
outcomes of the one thing another sub-core can change before its turn
(whether an MSHR row is free), and every sub-core's own warp, port and
scheduler updates are applied at once after all have issued
(``_retire``).  What sub-cores share — the MSHR rows, the L1 and the
address set — they still reach one after another, 0, 1, …
(``_issue_subcore``): that order is part of the timing model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sim.config import (BAR, LDG, N_UNITS, SCHED_GTO, STG, DynConfig,
                              StaticConfig, UNIT_OF_CLASS)
from repro.sim.trace import gen_address

BIG = jnp.int32(1 << 30)
# linear probes of the address set before an insert counts as overflow
ADDRSET_PROBES = 4


def _onehot(i, n: int):
    """(n,) bool, True at index ``i`` (nowhere if ``i`` is out of range)."""
    return jnp.arange(n, dtype=jnp.int32) == i


def _lookup(table, i):
    """``table[i]`` of a small static-length table as a select chain.
    ``table`` is a sequence of values (or of arrays that broadcast against
    ``i``) or a traced 1-D array; ``i`` is clamped into the table."""
    n = len(table)
    i = jnp.clip(i, 0, n - 1)
    out = jnp.broadcast_to(jnp.asarray(table[n - 1], jnp.int32), i.shape)
    for c in range(n - 2, -1, -1):
        out = jnp.where(i == c, table[c], out)
    return out


def _deliver(warp, req, t):
    """Deliver resolved responses for this SM. req fields: (M,)."""
    done = (req["stage"] == 3) & (req["t"] <= t)
    back = done & ~req["is_store"]
    slots = jnp.arange(warp["pending"].shape[0], dtype=jnp.int32)
    dec = jnp.sum((slots[:, None] == req["warp"][None, :]) & back[None, :],
                  axis=1, dtype=jnp.int32)                    # (W,)
    warp = dict(warp, pending=warp["pending"] - dec)
    req = dict(req, stage=jnp.where(done, 0, req["stage"]))
    return warp, req


def _release_barriers(warp, n_instr, t):
    """CTA barrier: a waiting warp resumes once every active warp of its
    CTA has either arrived at the barrier or finished the kernel (uniform
    control flow — all warps execute the same trace).  Pairwise over the
    warp slots of one SM: O(W²) booleans, entirely SM-local."""
    cta = warp["cta"]
    active = warp["active"]
    arrived = warp["wait_bar"] | (warp["pc"] >= n_instr)
    same = active[None, :] & (cta[:, None] == cta[None, :])   # (W, W)
    n_same = jnp.sum(same, axis=1)
    n_arr = jnp.sum(same & arrived[None, :], axis=1)
    release = warp["wait_bar"] & (n_arr == n_same)
    return dict(warp,
                wait_bar=jnp.where(release, False, warp["wait_bar"]),
                ready_at=jnp.where(release, t, warp["ready_at"]))


def _grid(x, cfg: StaticConfig):
    """A warp-slot axis as (slot within sub-core, sub-core): [k, s] is warp
    slot s + k·n_subcores, the k-th warp of sub-core s (GPUConfig checks
    that the sub-cores divide the slots)."""
    return x.reshape(cfg.warps_per_sm // cfg.n_subcores, cfg.n_subcores)


def _fetch(warp, trace, cfg: StaticConfig):
    """Every warp slot's instruction at its pc (clamped into the kernel):
    class, unit, block address if it is a memory op, and whether the next
    instruction depends on it — the cycle's only reads of the trace
    tables.  Ragged layout (core/batch.py:concat_kernels): instruction
    arrays are flat across kernels, so fetch at ``instr_base + pc``; pc
    itself STAYS kernel-local — address generation hashes it, so
    offsetting pc would change simulated addresses and break
    bit-exactness vs padded runs."""
    n_instr = trace["n_instr"]
    base = trace["instr_base"] if "instr_base" in trace else 0
    pcc = jnp.clip(warp["pc"], 0, n_instr - 1)
    # one gather of a row per warp from the tables side by side, the
    # dependency flags shifted up a slot so that a row holds its next
    # instruction's (the kernel's slots lie inside the flat array, so
    # slot pc + 1 is the next one wherever pc + 1 < n_instr).  The table
    # is loop-invariant: XLA builds it once, outside the cycle loop.
    dep = trace["dep"]
    table = jnp.stack([trace["ops"], trace["addr_mode"], trace["addr_param"],
                       jnp.concatenate([dep[1:], jnp.zeros_like(dep[:1])])
                       .astype(jnp.int32)], axis=1)
    row = table[base + pcc]
    op = row[:, 0]
    gwarp = warp["cta"] * trace["warps_per_cta"] + warp["wic"]
    return {"pcc": pcc, "op": op, "unit": _lookup(UNIT_OF_CLASS, op),
            "addr": gen_address(row[:, 1], row[:, 2], gwarp, pcc,
                                cfg.mem_blocks),
            "dep_next": (pcc + 1 < n_instr) & (row[:, 3] != 0)}


def _select(warp, sm, fetch, trace, t, cfg: StaticConfig, dyn: DynConfig):
    """Each sub-core's scheduler pick, for both outcomes of its one
    shared input: whether an MSHR row is free when its turn comes.  What
    else decides a pick — its own warps' state, unit ports and last issue
    — no other sub-core touches, so the picks of all sub-cores are made
    at once, before any issues.  Returns ``(exists, picks)``: whether
    each sub-core has a warp left, (nsc,); and the picked warp's fields,
    each (2, nsc) — row 0 with a free row, row 1 without (memory ops
    gated off).  A sub-core with no candidate picks its first slot, with
    ``do`` False."""
    nsc, n_warps = cfg.n_subcores, cfg.warps_per_sm
    g = {k: _grid(v, cfg) for k, v in dict(warp, **fetch).items()}
    exists = g["active"] & (g["pc"] < trace["n_instr"])
    blocked = (g["wait_mem"] & (g["pending"] > 0)) | g["wait_bar"]
    free = sm["unit_free"]                              # (nsc, N_UNITS)
    ufree = _lookup([free[:, u][None, :] for u in range(N_UNITS)],
                    g["unit"]) <= t
    ready = exists & ~blocked & (g["ready_at"] <= t) & ufree
    is_mem = (g["op"] == LDG) | (g["op"] == STG)
    cand = jnp.stack([ready, ready & ~is_mem])          # (2, K, nsc)

    # scheduler: GTO (greedy warp first, then oldest) or loose round-robin.
    # The selector is a traced value so one compiled program serves both —
    # and a vmapped sweep can mix GTO and LRR lanes.
    w_ids = _grid(jnp.arange(n_warps, dtype=jnp.int32), cfg)
    last = sm["last_issued"][None, :]
    key_gto = jnp.where(w_ids == last, -1, w_ids)
    key_lrr = (w_ids - last - 1) % n_warps
    key = jnp.where(dyn.core.sched == SCHED_GTO, key_gto, key_lrr)
    sel = jnp.argmin(jnp.where(cand, key, BIG), axis=1).astype(jnp.int32)
    hot = jnp.arange(n_warps // nsc, dtype=jnp.int32)[None, :, None] \
        == sel[:, None, :]                              # (2, K, nsc)

    def pick(x):
        return jnp.sum(jnp.where(hot, x, 0), axis=1, dtype=x.dtype)

    picks = {"do": jnp.any(hot & cand, axis=1), "sel": sel,
             "pc": pick(g["pcc"]), "op": pick(g["op"]),
             "unit": pick(g["unit"]), "addr": pick(g["addr"]),
             "dep_next": jnp.any(hot & g["dep_next"], axis=1)}
    return jnp.any(exists, axis=0), picks


def _l1_access(sm, addr, t, enable, cfg: StaticConfig):
    """One L1 probe for a scalar addr; the set and way it touches are
    written only where ``enable``. Returns (hit, sm_state')."""
    sets, ways = cfg.l1_sets, cfg.l1_ways
    srow = _onehot((addr % sets).astype(jnp.int32), sets)[:, None]
    match = srow & (sm["l1_tag"] == addr)                     # (sets, ways)
    hit = jnp.any(match)
    # the first matching way, else the set's least recently used one
    # (times are below INT32_MAX, so other sets never win)
    cell = jnp.where(hit, jnp.argmax(match.ravel()), jnp.argmin(
        jnp.where(srow, sm["l1_lru"], jnp.iinfo(jnp.int32).max).ravel()))
    cell = (jnp.arange(sets * ways, dtype=jnp.int32).reshape(sets, ways)
            == cell) & enable
    return hit, dict(sm,
                     l1_tag=jnp.where(cell & ~hit, addr, sm["l1_tag"]),
                     l1_lru=jnp.where(cell, t, sm["l1_lru"]))


def _addrset_insert(sm, addr, enable, cfg: StaticConfig):
    """Bounded open-addressing set insert (the paper's set-valued stat,
    'per-SM instance + terminal union' strategy): ``ADDRSET_PROBES``
    linear probes from the hash slot; the first probe that finds ``addr``
    or a free slot ends the insert, and none means overflow.  Every slot
    holds its probe distance from the hash slot, so the first such probe
    is one min-reduction over the set."""
    cap = cfg.addrset_cap
    aset = sm["addrset"]
    idx = (addr.astype(jnp.uint32) * jnp.uint32(2654435761)
           % jnp.uint32(cap)).astype(jnp.int32)
    slots = jnp.arange(cap, dtype=jnp.int32)
    dist = jnp.where(slots >= idx, slots - idx, slots - idx + cap)
    ends = (dist < ADDRSET_PROBES) & ((aset == addr) | (aset == -1))
    first = jnp.min(jnp.where(ends, dist, ADDRSET_PROBES))
    found = first < ADDRSET_PROBES
    put = enable & found & (dist == first) & (aset == -1)
    over = jnp.where(enable & ~found, 1, 0)
    return dict(sm, addrset=jnp.where(put, addr, aset),
                addrset_over=sm["addrset_over"] + over)


def _issue_subcore(sm, req, picks, t, sc, cfg: StaticConfig,
                   dyn: DynConfig):
    """Sub-core `sc`'s turn at the state its issue shares with the other
    sub-cores — the L1, the address set and the MSHR rows — in sub-core
    order.  Returns the pick that issued, with the L1 outcome."""
    m = cfg.mshr_per_sm
    first_free = jnp.min(jnp.where(req["stage"] == 0,
                                   jnp.arange(m, dtype=jnp.int32), m))
    free = first_free < m
    p = {k: jnp.where(free, v[0, sc], v[1, sc]) for k, v in picks.items()}

    mem_issue = p["do"] & ((p["op"] == LDG) | (p["op"] == STG))
    hit, sm = _l1_access(sm, p["addr"], t, mem_issue, cfg)
    sm = _addrset_insert(sm, p["addr"], mem_issue, cfg)
    l1_miss = mem_issue & ~hit

    # MSHR allocation on miss: the first free row (a memory op issues only
    # when there is one)
    put = _onehot(first_free, m) & l1_miss
    req = dict(
        req,
        stage=jnp.where(put, 1, req["stage"]),
        addr=jnp.where(put, p["addr"], req["addr"]),
        t=jnp.where(put, t + dyn.icnt.icnt_lat, req["t"]),
        warp=jnp.where(put, sc + cfg.n_subcores * p["sel"], req["warp"]),
        is_store=jnp.where(put, p["op"] == STG, req["is_store"]),
    )
    return sm, req, dict(p, hit=hit, mem_issue=mem_issue)


def _retire(warp, sm, stats, exists, issued, t, cfg: StaticConfig,
            dyn: DynConfig):
    """Apply every sub-core's issue to its own warp slot, unit ports and
    scheduler state, and count it.  ``issued`` holds each sub-core's
    result of ``_issue_subcore``, stacked to (nsc,)."""
    nsc, n_warps = cfg.n_subcores, cfg.warps_per_sm
    do, op, hit = issued["do"], issued["op"], issued["hit"]
    l1_miss = issued["mem_issue"] & ~hit
    lat = _lookup(dyn.core.lat, op)
    lat = jnp.where(op == LDG, jnp.where(hit, dyn.cache.l1_hit_lat, 1), lat)
    dep_next = issued["dep_next"]
    wait_lat = jnp.where(dep_next, jnp.maximum(lat, 1), 1)
    new_wait = dep_next & l1_miss          # wait on outstanding loads

    wo = (jnp.arange(n_warps // nsc, dtype=jnp.int32)[:, None]
          == issued["sel"][None, :]) & do[None, :]      # (K, nsc)

    def put(x, new):
        return jnp.where(wo, new, _grid(x, cfg)).reshape(n_warps)

    warp = dict(
        warp,
        pc=put(warp["pc"], issued["pc"] + 1),
        ready_at=put(warp["ready_at"], t + wait_lat),
        wait_mem=put(warp["wait_mem"], new_wait),
        wait_bar=put(warp["wait_bar"], _grid(warp["wait_bar"], cfg)
                     | (op == BAR)),
        pending=put(warp["pending"], _grid(warp["pending"], cfg)
                    + jnp.where(l1_miss & (op == LDG), 1, 0)),
    )
    port = do[:, None] & (jnp.arange(N_UNITS, dtype=jnp.int32)[None, :]
                          == issued["unit"][:, None])   # (nsc, N_UNITS)
    sm = dict(
        sm,
        unit_free=jnp.where(port, t + _lookup(dyn.core.disp, op)[:, None],
                            sm["unit_free"]),
        last_issued=jnp.where(
            do, jnp.arange(nsc, dtype=jnp.int32) + nsc * issued["sel"],
            sm["last_issued"]),
    )

    def count(x):
        return jnp.sum(x, dtype=jnp.int32)

    return warp, sm, dict(
        stats,
        issued=stats["issued"] + count(do),
        issued_mem=stats["issued_mem"] + count(issued["mem_issue"]),
        l1_hit=stats["l1_hit"] + count(issued["mem_issue"] & hit),
        l1_miss=stats["l1_miss"] + count(l1_miss),
        stall=stats["stall"] + count(exists & ~do),
        cycles_issue=stats["cycles_issue"] + jnp.where(jnp.any(do), 1, 0),
        warp_cycles=stats["warp_cycles"]
        + jnp.sum(warp["active"], dtype=jnp.int32),
    )


def sm_cycle_single(warp, sm, req, stats, trace, t, cfg: StaticConfig,
                    dyn: DynConfig):
    """One cycle of one SM (arrays without the n_sm axis)."""
    warp, req = _deliver(warp, req, t)
    warp = _release_barriers(warp, trace["n_instr"], t)
    exists, picks = _select(warp, sm, _fetch(warp, trace, cfg), trace, t,
                            cfg, dyn)
    issued = []
    for sc in range(cfg.n_subcores):
        sm, req, did = _issue_subcore(sm, req, picks, t, sc, cfg, dyn)
        issued.append(did)
    issued = {k: jnp.stack([d[k] for d in issued]) for k in issued[0]}
    warp, sm, stats = _retire(warp, sm, stats, exists, issued, t, cfg, dyn)
    return warp, sm, req, stats


def sm_quantum_single(warp, sm, req, stats, trace, t0, cfg: StaticConfig,
                      dyn: DynConfig):
    """Run Δ consecutive cycles for one SM — the communication window."""
    def body(i, carry):
        warp, sm, req, stats = carry
        return sm_cycle_single(warp, sm, req, stats, trace, t0 + i, cfg, dyn)

    return jax.lax.fori_loop(0, cfg.quantum, body, (warp, sm, req, stats))
