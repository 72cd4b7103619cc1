"""Memory-system unit tests: the max-plus queueing recurrence is exact."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.sim.config import TINY, split_config
from repro.sim.memsys import mem_phase
from repro.sim.state import init_state


def _mem_phase_at(t0: int):
    """One mem_phase call with contended L2 + DRAM traffic whose event
    times sit in [t0, t0+Δ).  Returns (req', mem', stats') — everything a
    time-shift-invariance check needs."""
    scfg, dyn = split_config(TINY)
    state = init_state(scfg)
    req, mem = state["req"], state["mem"]
    ns, m = req["stage"].shape

    stage = np.zeros((ns, m), np.int32)
    addr = np.zeros((ns, m), np.int32)
    t = np.zeros((ns, m), np.int32)
    # stage-1: six requests to ONE L2 slice (addr % l2_slices == 0) with
    # interleaved times + a tie — service order is everything here
    for i, (sm, row, a, dt) in enumerate([
            (0, 0, 4, 7), (1, 1, 8, 3), (2, 0, 12, 3),
            (3, 2, 16, 11), (5, 1, 20, 0), (7, 3, 24, 5)]):
        stage[sm, row], addr[sm, row], t[sm, row] = 1, a, t0 + dt
    # stage-2: six requests to ONE DRAM channel with clashing rows —
    # misordering flips the row-hit pattern and every finish time
    for sm, row, bank_row, dt in [(0, 4, 5, 2), (1, 5, 9, 6), (2, 4, 5, 1),
                                  (4, 4, 7, 9), (6, 4, 9, 4), (7, 5, 5, 13)]:
        stage[sm, row], addr[sm, row] = 2, 64 * bank_row
        t[sm, row] = t0 + dt
    req = dict(req, stage=jnp.asarray(stage), addr=jnp.asarray(addr),
               t=jnp.asarray(t))
    out_req, out_mem, out_stats = mem_phase(req, mem, state["stats"],
                                            jnp.int32(t0), scfg, dyn)
    return jax.tree_util.tree_map(np.asarray, (out_req, out_mem, out_stats))


def test_mem_phase_time_shift_invariance_past_int32_overflow():
    """Regression for the packed-key int32 overflow: with ABSOLUTE event
    time in the canonical-order key, t ~ 2^25 × (r = n_sm·mshr rows) crosses
    2^31 and the service order silently scrambles.  Keying on
    quantum-relative time makes mem_phase exactly shift-equivariant: a
    run far past the old overflow point must replay the t0=0 run with
    every event time shifted by t0 and bit-identical stats."""
    t_big = (1 << 25) - 8          # keys straddle 2^31 under the old code
    req0, mem0, stats0 = _mem_phase_at(0)
    reqb, memb, statsb = _mem_phase_at(t_big)

    assert (reqb["stage"] == req0["stage"]).all()
    touched = req0["stage"] >= 2       # DRAM-bound misses + completed
    assert (req0["stage"] == 3).any() and (req0["stage"] == 2).any()
    assert (reqb["t"][touched] - req0["t"][touched] == t_big).all()
    for k in stats0:
        assert statsb[k] == stats0[k], k
    assert (memb["l2_tag"] == mem0["l2_tag"]).all()
    assert (memb["dram_row"] == mem0["dram_row"]).all()
