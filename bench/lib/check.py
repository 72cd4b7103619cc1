"""The comparison that decides `correct`: the program's statistics of the
sampled lanes of every call in the window against the plain reference
(``reference.py``) run once over each sampled lane's timing point.

Each statistic is a number compared: the largest absolute gap between
program and reference over the compared lanes and calls. Every lane of
every call is held besides to what the workload fixes without a
simulation: each warp issues each of its instructions once, and every
CTA is launched. The model is integer and deterministic, so each limit
is 0 (an exact comparison), and a kernel of the program that ran into
the loop's horizon (``timeouts``, over every lane) fails too.
"""
from __future__ import annotations

import reference

STATS = ("issued", "issued_mem", "l1_hit", "l1_miss", "l2_hit", "l2_miss",
         "dram_req", "dram_row_hit", "ctas_launched", "cycles",
         "unique_addrs", "cycles_issue", "stall", "warp_cycles")
KEYS = STATS + ("every_lane_issued", "every_lane_ctas", "timeouts")
LIMITS = {k: 0 for k in KEYS}


def reference_stats(cfg: dict, points: list, max_cycles: int,
                    quantum: int | None = None) -> list:
    """The reference's statistics for each timing point, run as lanes of
    one batch. ``quantum`` overrides Δ; the control uses it."""
    return reference.simulate(cfg["gpu"], cfg["classes"],
                              cfg["unit_of_class"],
                              cfg["workload"]["kernels"], points,
                              max_cycles=max_cycles, quantum=quantum)


def fixed_counts(cfg: dict) -> dict:
    """What the workload fixes: warp-instructions issued, CTAs launched."""
    ks = cfg["workload"]["kernels"]
    return {"issued": sum(k["n_ctas"] * k["warps_per_cta"] * len(k["body"])
                          * k["repeats"] for k in ks),
            "ctas_launched": sum(k["n_ctas"] for k in ks)}


def gaps(cfg: dict, calls: list, lanes: list, ref: list) -> dict:
    """``calls``: per call, the program's stats of every lane; ``lanes``:
    the compared lanes, in the order of ``ref``. Returns each number."""
    fixed = fixed_counts(cfg)
    out = {k: 0 for k in KEYS}
    for stats in calls:
        for i, want in zip(lanes, ref, strict=True):
            for k in STATS:
                out[k] = max(out[k], abs(int(stats[i][k]) - int(want[k])))
        for got in stats:
            out["every_lane_issued"] = max(
                out["every_lane_issued"], abs(got["issued"] - fixed["issued"]))
            out["every_lane_ctas"] = max(
                out["every_lane_ctas"],
                abs(got["ctas_launched"] - fixed["ctas_launched"]))
            out["timeouts"] = max(out["timeouts"], int(got["timeouts"]))
    return out


def verdict(gap: dict) -> tuple:
    """(correct, the numbers compared with their limits)."""
    numbers = {k: {"value": gap[k], "limit": LIMITS[k]} for k in KEYS}
    return all(gap[k] <= LIMITS[k] for k in KEYS), numbers
