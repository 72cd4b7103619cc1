"""The plain reference agrees with the program bit for bit on small
machines, and its control (the same model with a coarser quantum, Δ = 2 ×
icnt_lat, which breaks the exactness window the configuration states)
fails the comparison.

The reference charges each kernel the cycles from its start to its end,
so a workload's ``cycles`` is its final clock. The program charges each
kernel its absolute end time, so the two agree on one-kernel workloads
only; on several kernels the program's own final clock sides with the
reference."""
import dataclasses

import pytest

import check
import reference
import tinyroot
from repro.core import stats as S
from repro.core.engine import simulate
from repro.core.parallel import make_sm_runner
from repro.sim.config import CLASS_NAMES, TINY, UNIT_OF_CLASS
from repro.workloads.synthetic import make_workload

LRR = dataclasses.replace(TINY, scheduler="lrr", l2_lat=7, icnt_lat=20,
                          dram_row_penalty=9,
                          lat_of_class=(3, 5, 21, 9, 0, 0, 2),
                          disp_of_class=(2, 1, 6, 3, 1, 2, 1))


def as_data(workload) -> list:
    return [{"n_ctas": k.n_ctas, "warps_per_cta": k.warps_per_cta,
             "repeats": 1,
             "body": [[CLASS_NAMES[int(o)], bool(d),
                       reference.ADDR_MODES[int(m)], int(p)]
                      for o, d, m, p in zip(k.ops, k.dep, k.addr_mode,
                                            k.addr_param)]}
            for k in workload.kernels]


def point(cfg) -> dict:
    return {"lat": list(cfg.lat_of_class), "disp": list(cfg.disp_of_class),
            "sched": cfg.scheduler,
            **{k: getattr(cfg, k) for k in (
                "l1_hit_lat", "l2_lat", "part_lat", "dram_burst",
                "dram_row_penalty", "icnt_lat")}}


@pytest.mark.parametrize("name,cfg", [
    ("stencil_bar", TINY), ("sssp", TINY), ("gemm", LRR),
    ("hybridsort", LRR), ("lavaMD", TINY), ("lavaMD", LRR),
    ("hotspot", LRR), ("mst", TINY)])
def test_reference_matches_program(name, cfg):
    w = make_workload(name, 0.02)
    final = simulate(w, cfg, make_sm_runner(cfg, "vmap"))
    got = S.finalize(final)
    gpu = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    want, = reference.simulate(gpu, list(CLASS_NAMES), list(UNIT_OF_CLASS),
                               as_data(w), [point(cfg)])
    same = [k for k in S.comparable(got) if k != "cycles"]
    assert {k: got[k] for k in same} == {k: want[k] for k in same}
    assert want["cycles"] == int(final["ctrl"]["cycle"])
    if len(w.kernels) == 1:
        assert got["cycles"] == want["cycles"]
    assert got["timeouts"] == want["timeouts"] == 0


def test_lanes_of_one_batch_match_solo_runs():
    cfg = tinyroot.tiny_config()
    base = point(TINY)
    pts = [base, dict(base, sched="lrr", l2_lat=12), dict(base, l2_lat=60)]
    batch = check.reference_stats(cfg, pts, 1 << 20)
    for p, b in zip(pts, batch):
        assert check.reference_stats(cfg, [p], 1 << 20) == [b]


def test_control_is_not_correct():
    cfg = tinyroot.tiny_config()
    pts = [point(TINY)]
    ref = check.reference_stats(cfg, pts, 1 << 20)
    control = check.reference_stats(cfg, pts, 1 << 20,
                                    quantum=2 * TINY.icnt_lat)
    gap = check.gaps(cfg, [control], [0], ref)
    correct, numbers = check.verdict(gap)
    assert not correct
    assert numbers["cycles"]["value"] > 0
