"""The trace reduction, on a small trace recorded on the CPU (kept in
data/) and on hand-made events."""
from pathlib import Path

import numpy as np
import pytest

import xtrace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def events():
    return xtrace.load(str(DATA))


def test_fixture_loads_ops_and_spans(events):
    assert len(events["chips"]) == 1
    assert len(events["chips"][0]) == 14        # the while loop left out
    assert sorted(n for n, _, _ in events["spans"]) == [
        "bench.block_until_ready", "bench.dispatch", "bench.traced_call"]


def test_fixture_busy_idle_and_counts(events):
    red = xtrace.reduce(events, "bench.traced_call")
    (lo, hi), = [(s, e) for n, s, e in events["spans"]
                 if n == "bench.traced_call"]
    mask = np.zeros(hi - lo, bool)             # busy, nanosecond by ns
    for _, s, e in events["chips"][0]:
        mask[max(s, lo) - lo:min(e, hi) - lo] = True
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert red["busy_s"] == pytest.approx(mask.sum() / 1e9)
    assert red["busy_s"] == pytest.approx(3.1987e-05)
    assert red["chips"][0]["n_ops"] == 14
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(
        1 - mask.mean())
    names = [n for n, _ in red["device_ops"]]
    assert names[:2] == ["dot_general.4", "broadcast_add_fusion"]
    assert red["device_ops"][0][1] == pytest.approx(1.1711e-05)
    gaps = red["idle_gaps"]
    assert gaps[0][0] == "bench.dispatch"
    assert len(gaps) == 10
    assert [d for _, d in gaps] == sorted((d for _, d in gaps), reverse=True)
    assert sum(d for _, d in gaps) <= red["window_s"] - red["busy_s"] + 1e-12
    idle = np.flatnonzero(np.diff(np.concatenate(
        ([0], (~mask).astype(int), [0]))) != 0).reshape(-1, 2)
    assert gaps[0][1] == pytest.approx((idle[:, 1] - idle[:, 0]).max() / 1e9)


def test_op_names_drop_control_flow():
    assert xtrace.op_name(
        "%fusion.16 = s32[80,32]{0,1:T(8,128)} fusion(s32[80,32] %p)") == \
        "fusion.16"
    assert xtrace.op_name(
        "%while.3 = (s32[], s32[80]) while((s32[], s32[80]) %t)") is None
    assert xtrace.op_name("%custom-call.2 = s32[2] custom-call(s32[2] %x)") \
        == "custom-call.2"
    assert xtrace.op_name("while.1") is None
    assert xtrace.op_name("all-gather-start.4") == "all-gather-start.4"


def test_union_clips_and_merges():
    assert xtrace.union([(5, 9), (0, 3), (2, 4), (8, 20)], 1, 15) == [
        [1, 4], [5, 15]]
    assert xtrace.union([(0, 1)], 2, 5) == []


def test_two_chips_busy_and_gaps():
    ev = {"spans": [("bench.traced_call", 0, 100), ("bench.dispatch", 0, 10),
                    ("bench.block_until_ready", 10, 100)],
          "chips": [[("fusion.1", 10, 40), ("all-gather.3", 40, 60)],
                    [("fusion.1", 20, 50), ("all-gather-start.2", 55, 75),
                     ("fusion.9", 150, 160)]]}
    red = xtrace.reduce(ev, "bench.traced_call")
    assert red["window_s"] == pytest.approx(100e-9)
    assert [c["busy_s"] for c in red["chips"]] == pytest.approx(
        [50e-9, 50e-9])
    assert [c["n_ops"] for c in red["chips"]] == [2, 2]
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    longest = red["idle_gaps"][0]
    assert longest == ["bench.block_until_ready", pytest.approx(40e-9)]
    assert xtrace.span_at(ev["spans"], 5) == "bench.dispatch"
    assert xtrace.span_at(ev["spans"], 500) == "no_span"
