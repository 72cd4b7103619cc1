"""Plain reference of the simulator's timing model, in NumPy.

It imports nothing of the program under test and takes nothing that the
program made. It reads the same configuration file the harness hands the
program (widths, per-class tables, kernels as data) and one timing point
per lane, and returns the statistics that decide `correct`.

The model it implements, per machine quantum of Δ cycles:

1. memory phase over the whole request table: requests due at an L2 slice
   inside [t0, t0+Δ) are served one per cycle per slice in the order
   (slice, arrival, row id); tags are probed against the quantum's start
   snapshot, hits refresh the line's LRU stamp, and of several misses to
   one set only the last in that order fills the LRU way. Misses then
   queue at their DRAM channel in the order (channel, arrival, row id),
   with a row-buffer hit costing one burst and a miss burst + penalty.
2. CTA dispatch: finished warps free their slots; CTAs are dealt one per
   SM per round, round robin from a pointer that advances every quantum,
   into the lowest free warp slots.
3. Δ cycles of the SM phase. Each cycle: deliver responses that are due,
   release CTA barriers, then each sub-core in turn issues at most one
   instruction (GTO or LRR), probing the SM's L1 on loads and stores and
   taking a free MSHR row on a miss.

A kernel runs until every CTA was dispatched and no warp or request is
live, checked at quantum ends. The clock runs on across kernels, L2 and
DRAM keep their state, and each kernel is charged the cycles from its
start to its finish, so a workload's ``cycles`` is its final clock (as
Accel-sim's total simulated cycles sum its kernels' cycles). The memory phase is a plain loop
over requests in their service order; the SM phase is NumPy over the SMs
of every lane, each SM going from one cycle in which something can
happen to the next (a quantum's SMs touch nothing of each other). There
is no sort network, scan or scatter trick, so it shares no code path
with the program.
"""
from __future__ import annotations

import numpy as np

ADDR_MODES = ("none", "stream", "strided", "random")
BIG = 1 << 30
U32 = 0xFFFFFFFF


def expand_kernel(kernel: dict, classes: list) -> dict:
    """Instruction arrays of one kernel from its data: ``body`` rows of
    [class, depends_on_previous, address_mode, address_param], repeated."""
    body = kernel["body"] * int(kernel.get("repeats", 1))
    return {
        "ops": np.array([classes.index(r[0]) for r in body], np.int64),
        "dep": np.array([bool(r[1]) for r in body], bool),
        "mode": np.array([ADDR_MODES.index(r[2]) for r in body], np.int64),
        "param": np.array([int(r[3]) for r in body], np.int64),
        "n_ctas": int(kernel["n_ctas"]),
        "wpc": int(kernel["warps_per_cta"]),
    }


def address(mode, param, gwarp, pc, mem_blocks):
    """Block address of a memory instruction (int64 arrays in, out)."""
    stream = (param * 4096 + gwarp * 8 + pc % 8) % mem_blocks
    strided = (param * 4096 + gwarp * 257 + pc * 31) % mem_blocks
    h = ((gwarp * 2654435761) + (pc * 40503 + param * 97)) & U32
    rand = h % mem_blocks
    return np.where(mode == 1, stream, np.where(mode == 2, strided, rand))


class Machine:
    """State of B lanes of one modelled GPU, SM arrays flattened to
    N = B × n_sm rows so that the SM phase is NumPy over every SM of every
    lane at once."""

    def __init__(self, gpu: dict, classes: list, unit_of_class: list,
                 lanes: list, quantum: int | None = None):
        g = gpu
        self.B = len(lanes)
        self.S = g["n_sm"]
        self.W = g["warps_per_sm"]
        self.SC = g["n_subcores"]
        self.M = g["mshr_per_sm"]
        self.N = self.B * self.S
        self.q = int(quantum if quantum is not None else g["quantum"])
        self.g = g
        self.classes = list(classes)
        self.unit_of = np.array(unit_of_class, np.int64)
        self.n_units = int(self.unit_of.max()) + 1
        self.c_ldg = self.classes.index("ldg")
        self.c_stg = self.classes.index("stg")
        self.c_bar = self.classes.index("bar")
        B, S, W, M, N = self.B, self.S, self.W, self.M, self.N

        def lane_col(key):
            return np.array([int(d[key]) for d in lanes], np.int64)

        # per-lane timing point, and its copy per SM row
        self.lat = np.array([d["lat"] for d in lanes], np.int64)     # (B,7)
        self.disp = np.array([d["disp"] for d in lanes], np.int64)
        self.gto = np.array([d["sched"] == "gto" for d in lanes])
        self.l1_hit_lat = lane_col("l1_hit_lat")
        self.l2_lat = lane_col("l2_lat")
        self.part_lat = lane_col("part_lat")
        self.dram_burst = lane_col("dram_burst")
        self.row_penalty = lane_col("dram_row_penalty")
        self.icnt_lat = lane_col("icnt_lat")
        rep = lambda a: np.repeat(a, S, axis=0)  # noqa: E731
        self.n_lat, self.n_disp = rep(self.lat), rep(self.disp)
        self.n_gto, self.n_l1lat = rep(self.gto), rep(self.l1_hit_lat)
        self.n_icnt = rep(self.icnt_lat)
        # Slot j of a row here is sub-core j // K's (j % K)-th warp; the
        # modelled GPU numbers that warp k * SC + sc (sub-core w % SC), and
        # its numbers order the schedulers' keys and the slots CTAs fill.
        K = W // self.SC
        self.wids = (np.arange(K)[None, :] * self.SC
                     + np.arange(self.SC)[:, None])[None]      # (1, SC, K)
        self.K = K
        self.fill_order = np.argsort(self.wids.ravel())

        i64 = np.int64
        self.pc = np.zeros((N, W), i64)
        self.active = np.zeros((N, W), bool)
        self.ready_at = np.zeros((N, W), i64)
        self.pending = np.zeros((N, W), i64)
        self.wait_mem = np.zeros((N, W), bool)
        self.wait_bar = np.zeros((N, W), bool)
        self.cta = np.full((N, W), -1, i64)
        self.wic = np.zeros((N, W), i64)
        self.last = np.full((N, self.SC), -1, i64)
        self.unit_free = np.zeros((N, self.SC, self.n_units), i64)
        self.l1_tag = np.full((N, g["l1_sets"], g["l1_ways"]), -1, i64)
        self.l1_lru = np.zeros((N, g["l1_sets"], g["l1_ways"]), i64)
        self.aset = np.full((N, g["addrset_cap"]), -1, i64)
        self.aset_over = np.zeros(N, i64)
        self.r_stage = np.zeros((N, M), i64)
        self.r_addr = np.zeros((N, M), i64)
        self.r_t = np.zeros((N, M), i64)
        self.r_warp = np.zeros((N, M), i64)
        self.r_store = np.zeros((N, M), bool)
        self.l2_tag = np.full((B, g["l2_slices"], g["l2_sets"], g["l2_ways"]),
                              -1, i64)
        self.l2_lru = np.zeros_like(self.l2_tag)
        self.l2_busy = np.zeros((B, g["l2_slices"]), i64)
        self.dram_busy = np.zeros((B, g["dram_channels"]), i64)
        self.dram_row = np.full((B, g["dram_channels"]), -1, i64)
        self.cycle = np.zeros(B, i64)
        self.rr = np.zeros(B, i64)
        self.sm_stats = {k: np.zeros(N, i64) for k in (
            "issued", "issued_mem", "l1_hit", "l1_miss", "cycles_issue",
            "stall", "warp_cycles")}
        self.g_stats = {k: np.zeros(B, i64) for k in (
            "l2_hit", "l2_miss", "dram_req", "dram_row_hit",
            "ctas_launched")}
        self.total = np.zeros(B, i64)
        self.timeouts = np.zeros(B, i64)
        # what refresh() keeps, per warp slot and per sub-core row
        self.elig = np.zeros((N, W), bool)
        self.e = np.zeros((N, W), i64)
        self.mem = np.zeros((N, W), bool)
        self.exists_any = np.zeros((N, self.SC), bool)
        self.n_active = np.zeros(N, i64)
        self.free = np.full(N, M, i64)
        view = lambda a: a.reshape(N, self.SC, K)  # noqa: E731
        for name in ("pc", "active", "ready_at", "pending", "wait_mem",
                     "wait_bar", "elig", "e", "mem"):
            setattr(self, name + "3", view(getattr(self, name)))
        self.all_n = np.repeat(np.arange(N), self.SC)
        self.all_sc = np.tile(np.arange(self.SC), N)

    # -- between kernels ----------------------------------------------------

    def reset_for_kernel(self):
        """Warps, requests, L1 and issue ports start afresh; L2, DRAM, the
        clock, the dispatch pointer and every statistic carry over."""
        self.pc[:] = 0
        self.active[:] = False
        self.ready_at[:] = 0
        self.pending[:] = 0
        self.wait_mem[:] = False
        self.wait_bar[:] = False
        self.cta[:] = -1
        self.wic[:] = 0
        self.last[:] = -1
        self.unit_free[:] = 0
        self.l1_tag[:] = -1
        self.l1_lru[:] = 0
        for a in (self.r_stage, self.r_addr, self.r_t, self.r_warp):
            a[:] = 0
        self.r_store[:] = False

    # -- memory phase -------------------------------------------------------

    def memory_phase(self, b: int, t0: int):
        g, S, M = self.g, self.S, self.M
        rows = slice(b * S, (b + 1) * S)
        stage, addr, t = (self.r_stage[rows], self.r_addr[rows],
                          self.r_t[rows])
        horizon = t0 + self.q
        slices, sets = g["l2_slices"], g["l2_sets"]

        due = np.argwhere((stage == 1) & (t < horizon))
        reqs = sorted(((int(addr[s, m]) % slices, int(t[s, m]), s * M + m,
                        s, m) for s, m in due))
        busy = self.l2_busy[b]
        tag0 = self.l2_tag[b].copy()          # tags as the quantum began
        lru = self.l2_lru[b]
        last = {}
        for slc, arr, _, s, m in reqs:
            a = int(addr[s, m])
            st = (a // slices) % sets
            start = max(arr, int(busy[slc]))
            busy[slc] = start + 1
            ways = tag0[slc, st]
            if (ways == a).any():
                lru[slc, st, int(np.argmax(ways == a))] = max(
                    int(lru[slc, st, int(np.argmax(ways == a))]), t0)
                stage[s, m] = 3
                t[s, m] = start + self.l2_lat[b] + self.icnt_lat[b]
                self.g_stats["l2_hit"][b] += 1
            else:
                last[(slc, st)] = a           # the last miss of a set fills
                stage[s, m] = 2
                t[s, m] = start + self.l2_lat[b] + self.part_lat[b]
                self.g_stats["l2_miss"][b] += 1
        for (slc, st), a in last.items():
            v = int(np.argmin(lru[slc, st]))
            self.l2_tag[b, slc, st, v] = a
            lru[slc, st, v] = t0

        chans = g["dram_channels"]
        due = np.argwhere((stage == 2) & (t < horizon))
        reqs = sorted((int(addr[s, m]) % slices * chans // slices,
                       int(t[s, m]), s * M + m, s, m) for s, m in due)
        dbusy, drow = self.dram_busy[b], self.dram_row[b]
        for ch, arr, _, s, m in reqs:
            row = int(addr[s, m]) // g["dram_row_div"]
            hit = row == drow[ch]
            service = self.dram_burst[b] + (0 if hit else self.row_penalty[b])
            finish = max(arr, int(dbusy[ch])) + service
            dbusy[ch] = finish
            drow[ch] = row
            stage[s, m] = 3
            t[s, m] = finish + self.part_lat[b] + self.icnt_lat[b]
            self.g_stats["dram_req"][b] += 1
            self.g_stats["dram_row_hit"][b] += int(hit)

    # -- CTA dispatch -------------------------------------------------------

    def dispatch(self, b: int, k: dict, next_cta: int) -> int:
        S = self.S
        rows = slice(b * S, (b + 1) * S)
        n_instr, wpc = len(k["ops"]), k["wpc"]
        act = self.active[rows]               # a view: frees finished warps
        act &= ~((self.pc[rows] >= n_instr) & (self.pending[rows] == 0))
        order = [(int(self.rr[b]) + i) % S for i in range(S)]
        self.rr[b] = (self.rr[b] + 1) % S
        if next_cta >= k["n_ctas"]:
            return next_cta
        free = ~act
        cap = np.minimum(free.sum(1) // wpc, self.g["max_cta_per_sm"])
        got = [[] for _ in range(S)]
        nxt = next_cta
        for r in range(int(cap.max())):
            for s in order:
                if cap[s] > r and nxt < k["n_ctas"]:
                    got[s].append(nxt)
                    nxt += 1
        for s in range(S):
            if not got[s]:
                continue
            order = self.fill_order
            slots = order[np.flatnonzero(free[s][order])][:len(got[s]) * wpc]
            n = b * S + s
            for j, w in enumerate(slots):
                self.active[n, w] = True
                self.pc[n, w] = 0
                self.ready_at[n, w] = self.cycle[b]
                self.pending[n, w] = 0
                self.wait_mem[n, w] = False
                self.wait_bar[n, w] = False
                self.cta[n, w] = got[s][j // wpc]
                self.wic[n, w] = j % wpc
        self.g_stats["ctas_launched"][b] += nxt - next_cta
        return nxt

    # -- SM phase -----------------------------------------------------------

    def refresh(self, n, sc, k: dict):
        """Recompute what decides whether the warps of sub-core rows
        (n[i], sc[i]) can issue: ``elig`` (the warp has an instruction
        left and waits on no load or barrier), ``e`` (the first cycle at
        which it is ready and its instruction's port is free) and ``mem``
        (that instruction is a load or store). Called for every row after
        dispatch, and for the rows an issue, a delivery or a barrier
        release touched."""
        n_instr = len(k["ops"])
        pc = self.pc3[n, sc]
        exists = self.active3[n, sc] & (pc < n_instr)
        code = k["code"][np.minimum(pc, n_instr - 1)]
        blocked = ((self.wait_mem3[n, sc] & (self.pending3[n, sc] > 0))
                   | self.wait_bar3[n, sc])
        self.elig3[n, sc] = exists & ~blocked
        self.mem3[n, sc] = (code & 8) > 0
        port = self.unit_free[n[:, None], sc[:, None], code & 7]
        self.e3[n, sc] = np.maximum(self.ready_at3[n, sc], port)
        self.exists_any[n, sc] = exists.any(1)

    def refresh_all(self, k: dict):
        self.refresh(self.all_n, self.all_sc, k)
        self.n_active = self.active.sum(1)
        self.free = (self.r_stage == 0).sum(1)

    def next_event(self, t: np.ndarray) -> np.ndarray:
        """Per row, the first cycle from t (per row) at which anything can
        happen: a warp is ready with its port free (a load or store also
        needs a free MSHR row), or a response is due. A row with a warp at
        a barrier steps every cycle. Between two events a row's warps stay
        as they are, and only its stall and warp-cycle counts grow."""
        ready = self.elig & ~(self.mem & (self.free == 0)[:, None])
        warp_t = np.where(ready, self.e, BIG).min(1)
        due_t = np.where(self.r_stage == 3, self.r_t, BIG).min(1)
        ev = np.maximum(np.minimum(warp_t, due_t), t)
        return np.where(self.wait_bar.any(1), t, ev)

    def sm_cycle(self, t: np.ndarray, k: dict, go: np.ndarray):
        """Cycle t[n] of every SM row n with ``go`` (rows may be at
        different cycles: an SM touches nothing of another inside a
        quantum).

        The (N, W) warp arrays are viewed as (N, SC, W/SC), one row of
        slots per sub-core. The sub-cores of an SM choose their warps
        independently, except that a load or store needs a free MSHR row
        after the earlier sub-cores' misses of this cycle: all pick at
        once, then the sub-cores' memory accesses run in sub-core order,
        and a sub-core that finds the rows used up picks again without
        memory instructions."""
        N, SC, K = self.N, self.SC, self.K
        n_instr = len(k["ops"])
        st = self.sm_stats
        tc = t[:, None]

        due = (self.r_stage == 3) & (self.r_t <= tc) & go[:, None]
        if due.any():
            n, m = np.nonzero(due & ~self.r_store)
            w = self.r_warp[n, m]
            np.subtract.at(self.pending, (n, w), 1)
            self.r_stage[due] = 0
            self.free += due.sum(1)
            rows = np.unique(n * SC + w // K)
            self.refresh(rows // SC, rows % SC, k)

        if self.wait_bar.any():
            fin = self.pc >= n_instr
            for n in np.flatnonzero(self.wait_bar.any(1) & go):
                arrived = self.wait_bar[n] | fin[n]
                for w in np.flatnonzero(self.wait_bar[n]):
                    same = self.active[n] & (self.cta[n] == self.cta[n, w])
                    if (same & ~arrived).sum() == 0:
                        self.wait_bar[n, w] = False
                        self.ready_at[n, w] = t[n]
                        self.refresh(np.array([n]), np.array([w // K]), k)

        cand = self.elig & (self.e <= tc) & go[:, None]
        nofree = self.free == 0
        if nofree.any():
            cand &= ~(self.mem & nofree[:, None])
        has = cand.reshape(N, SC, K).any(2)
        if not has.any():
            st["stall"] += self.exists_any.sum(1) * go
            return

        n, sc = np.nonzero(has)               # the sub-cores that can issue
        cand3 = cand.reshape(N, SC, K)
        w_ids = self.wids[0, sc]
        last = self.last[n, sc][:, None]
        key = np.where(self.n_gto[n, None],
                       np.where(w_ids == last, -1, w_ids),
                       (w_ids - last - 1) % self.W)
        sel = np.argmin(np.where(cand3[n, sc], key, BIG), axis=1)
        do = np.ones(len(n), bool)
        pc = self.pc3[n, sc, sel]
        op = k["ops"][pc]
        hit = np.zeros(len(n), bool)
        mem = (op == self.c_ldg) | (op == self.c_stg)
        for s in range(SC) if mem.any() else ():
            i = np.flatnonzero(mem & (sc == s))
            full = i[self.free[n[i]] == 0]
            if len(full):                     # rows used up: pick again
                nf = n[full]
                c2 = (self.elig3[nf, s] & (self.e3[nf, s] <= t[nf, None])
                      & ~self.mem3[nf, s])
                s_new = np.argmin(np.where(c2, key[full], BIG), axis=1)
                sel[full], do[full] = s_new, c2.any(1)
                pc[full] = np.minimum(self.pc3[nf, s, s_new], n_instr - 1)
                op[full] = k["ops"][pc[full]]
                mem[full] = False
                i = i[self.free[n[i]] > 0]
            if len(i):
                hit[i] = self._memory_access(n[i], s * K + sel[i], pc[i],
                                             op[i], t, k)
        miss = mem & ~hit
        n, sc, sel, pc, op = n[do], sc[do], sel[do], pc[do], op[do]
        hit, miss = hit[do], miss[do]
        do_sm = np.zeros((N, SC), bool)
        do_sm[n, sc] = True

        st["stall"] += (self.exists_any & ~do_sm).sum(1) * go
        st["cycles_issue"] += do_sm.any(1)
        st["issued"] += do_sm.sum(1)
        w, tn = sc * K + sel, t[n]
        lat = np.where(op == self.c_ldg, np.where(hit, self.n_l1lat[n], 1),
                       self.n_lat[n, op])
        nxt = pc + 1
        dep_next = (nxt < n_instr) & k["dep"][np.minimum(nxt, n_instr - 1)]
        self.ready_at[n, w] = tn + np.where(dep_next, np.maximum(lat, 1), 1)
        self.wait_mem[n, w] = dep_next & miss
        self.wait_bar[n, w] |= op == self.c_bar
        self.pending[n, w] += miss & (op == self.c_ldg)
        self.pc[n, w] = nxt
        self.unit_free[n, sc, self.unit_of[op]] = tn + self.n_disp[n, op]
        self.last[n, sc] = self.wids[0, sc, sel]
        self.refresh(n, sc, k)

    def sm_phase(self, t0: np.ndarray, k: dict, live: np.ndarray):
        """Δ cycles of every live SM row from t0 (per row): each row goes
        from event to event (``next_event``); the cycles between count
        toward its stall and warp-cycle totals only."""
        st = self.sm_stats
        end = t0 + self.q
        t = t0.copy()
        while True:
            ev = np.where(live, self.next_event(t), BIG)
            go = ev < end
            stop = np.where(go, ev, np.where(live, end, t))
            st["stall"] += self.exists_any.sum(1) * (stop - t)
            if not go.any():
                break
            self.sm_cycle(np.where(go, ev, t), k, go)
            t = np.where(go, ev + 1, stop)
        st["warp_cycles"] += self.n_active * self.q * live

    def _memory_access(self, n, w, pc, op, t, k):
        """Loads and stores of one sub-core on SM rows ``n`` (distinct):
        address, L1 probe and fill, address set, and an MSHR row on a miss.
        Returns the L1 hits."""
        g, st = self.g, self.sm_stats
        gwarp = self.cta[n, w] * k["wpc"] + self.wic[n, w]
        a = address(k["mode"][pc], k["param"][pc], gwarp, pc, g["mem_blocks"])
        s1 = a % g["l1_sets"]
        ways = self.l1_tag[n, s1]
        hit = (ways == a[:, None]).any(1)
        way = np.where(hit, np.argmax(ways == a[:, None], axis=1),
                       np.argmin(self.l1_lru[n, s1], axis=1))
        self.l1_tag[n, s1, way] = a
        self.l1_lru[n, s1, way] = t[n]
        self._addrset(n, a)
        st["issued_mem"][n] += 1
        st["l1_hit"][n] += hit
        st["l1_miss"][n] += ~hit
        nj, j = n[~hit], np.flatnonzero(~hit)
        if len(nj):                           # the first free row of the SM
            row = np.argmax(self.r_stage[nj] == 0, axis=1)
            self.r_stage[nj, row] = 1
            self.r_addr[nj, row] = a[j]
            self.r_t[nj, row] = t[nj] + self.n_icnt[nj]
            self.r_warp[nj, row] = w[j]
            self.r_store[nj, row] = op[j] == self.c_stg
            self.free[nj] -= 1
        return hit

    def _addrset(self, n, a):
        """Insert each address into its SM's bounded open-addressing set
        (4 linear probes; an address that finds no slot counts overflow)."""
        cap = self.g["addrset_cap"]
        h = (a * 2654435761 & U32) % cap
        for j in range(len(n)):
            row, addr = n[j], a[j]
            for p in range(4):
                cur = self.aset[row, (h[j] + p) % cap]
                if cur == addr:
                    break
                if cur == -1:
                    self.aset[row, (h[j] + p) % cap] = addr
                    break
            else:
                self.aset_over[row] += 1

    # -- kernels ------------------------------------------------------------

    def converged(self, b: int, k: dict, next_cta: int) -> bool:
        rows = slice(b * self.S, (b + 1) * self.S)
        n_instr = len(k["ops"])
        live = self.active[rows] & ~((self.pc[rows] >= n_instr)
                                     & (self.pending[rows] == 0))
        return (next_cta >= k["n_ctas"] and not live.any()
                and not (self.r_stage[rows] != 0).any())

    def run_kernel(self, k: dict, max_cycles: int):
        B, S = self.B, self.S
        if k["n_ctas"] == 0:
            return                            # a padding kernel: no charge
        self.reset_for_kernel()
        is_mem = (k["ops"] == self.c_ldg) | (k["ops"] == self.c_stg)
        k["code"] = k["ops"] * 16 + is_mem * 8 + self.unit_of[k["ops"]]
        next_cta = [0] * B
        done_at = [-1] * B
        start = self.cycle.copy()
        while True:
            live_l = [done_at[b] < 0 and self.cycle[b] < max_cycles
                      for b in range(B)]
            if not any(live_l):
                break
            for b in range(B):
                if live_l[b]:
                    self.memory_phase(b, int(self.cycle[b]))
                    next_cta[b] = self.dispatch(b, k, next_cta[b])
            self.refresh_all(k)
            live = np.repeat(np.array(live_l), S)
            self.sm_phase(np.repeat(self.cycle, S), k, live)
            for b in range(B):
                if live_l[b]:
                    self.cycle[b] += self.q
                    if self.converged(b, k, next_cta[b]):
                        done_at[b] = int(self.cycle[b])
        for b in range(B):
            end = done_at[b] if done_at[b] >= 0 else self.cycle[b]
            self.total[b] += end - start[b]
            self.timeouts[b] += done_at[b] < 0

    def stats(self) -> list:
        """Per lane: the statistics ``comparable()`` names, plus timeouts."""
        out = []
        for b in range(self.B):
            rows = slice(b * self.S, (b + 1) * self.S)
            d = {k: int(v[rows].sum()) for k, v in self.sm_stats.items()}
            d.update({k: int(v[b]) for k, v in self.g_stats.items()})
            d["cycles"] = int(self.total[b])
            aset = self.aset[rows]
            d["unique_addrs"] = int(np.unique(aset[aset >= 0]).size)
            d["timeouts"] = int(self.timeouts[b])
            out.append(d)
        return out


def simulate(gpu: dict, classes: list, unit_of_class: list, kernels: list,
             lanes: list, max_cycles: int = 1 << 20,
             quantum: int | None = None) -> list:
    """Run every kernel on every lane; returns one stats dict per lane.
    ``quantum`` overrides Δ (the control runs a coarser one)."""
    m = Machine(gpu, classes, unit_of_class, lanes, quantum)
    for k in kernels:
        m.run_kernel(expand_kernel(k, classes), max_cycles)
    return m.stats()
