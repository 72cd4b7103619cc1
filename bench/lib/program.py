"""What every traffic kind does with the program the same way: draw from
the seed, build relabeled and shortened initial states, read the quanta a
call ran, and reduce a final state to per-lane statistics with the
program's own ``finalize``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# the quantum loop's horizon: RunPlan's default max_cycles, which every
# runner used here compiles with
MAX_CYCLES = 1 << 20


def rng(seed: int) -> np.random.Generator:
    """The one generator a seed gives (PCG64, as launch/dse.py draws)."""
    return np.random.Generator(np.random.PCG64(int(seed)))


def sm_labels(n_sm: int, seed: int) -> np.ndarray:
    """The seed's relabeling of the SM axis: array position p holds SM
    ``labels[p]``. The program dispatches CTAs and orders requests by
    these original ids, so every relabeling gives the same statistics and
    the same work (``core/parallel.py:permute_state``)."""
    return rng(seed).permutation(n_sm).astype(np.int32)


def relabel(state: dict, labels) -> dict:
    """``state`` with the SM labels ``labels`` on every lane (traceable:
    an initial state's SM rows are all alike, so only the labels move)."""
    old = state["ctrl"]["sm_ids"]
    ids = jnp.broadcast_to(jnp.asarray(labels, old.dtype), old.shape)
    return dict(state, ctrl=dict(state["ctrl"], sm_ids=ids))


def with_cycle(state: dict, cycle: int) -> dict:
    """``state`` with its clock set to ``cycle`` on every lane, placed as
    before. A call from such a state runs (MAX_CYCLES − cycle) / Δ quanta
    before the loop's horizon stops it: a shorter call of the same
    program, with the same shapes."""
    old = state["ctrl"]["cycle"]
    new = jax.device_put(jnp.full(old.shape, cycle, old.dtype), old.sharding)
    return dict(state, ctrl=dict(state["ctrl"], cycle=new))


def quanta(start_cycle: int, final_state: dict, quantum: int) -> int:
    """Quanta the slowest lane ran from ``start_cycle`` to its end."""
    end = int(np.max(np.asarray(final_state["ctrl"]["cycle"])))
    return (end - start_cycle) // quantum


def lane_stats(final_state: dict, n_lanes: int, batched: bool) -> list:
    """The program's finalized statistics of each lane."""
    from repro.core.stats import finalize
    host = jax.device_get(final_state)
    if not batched:
        return [finalize(host)]
    return [finalize(jax.tree_util.tree_map(lambda x, i=i: x[i], host))
            for i in range(n_lanes)]
