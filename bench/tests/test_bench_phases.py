"""Device time by phase: the phase map of a compiled program's HLO text
and the split of a traced window by it (``lib/phases.py``), on a
one-quantum CPU trace of the small solo cell kept in phase_data/ (made by
``record_phases.py``) and on hand-made events; ``phase_split.py`` on the
small cell; and the readers of the compile metrics."""
import gzip
import json
import time
from pathlib import Path

import numpy as np
import pytest

import cells
import harness
import phases
import tinyroot
import xtrace
from repro.core import telemetry
from repro.core.engine import PHASES

DATA = Path(__file__).resolve().parent / "phase_data"
COMPILE_METRICS = ["trace_lower_s", "xla_compile_s"]


@pytest.fixture(scope="module")
def recorded():
    events = xtrace.load(str(DATA))
    with gzip.open(DATA / "program.hlo.gz", "rt") as f:
        pmap = phases.phase_map(f.read())
    return events, pmap


def mask_s(ops, lo, hi) -> float:
    """Seconds covered by ops that start in [lo, hi), ns by ns."""
    mask = np.zeros(hi - lo, bool)
    for _, s, e in ops:
        if lo <= s < hi:
            mask[s - lo:min(e, hi) - lo] = True
    return mask.sum() / 1e9


def test_every_phase_tags_the_recorded_program(recorded):
    _, pmap = recorded
    assert set(pmap.values()) == set(PHASES)


def test_recorded_phases_and_unscoped_add_up_to_busy(recorded):
    events, pmap = recorded
    parts = phases.split(events, "bench.traced_call", pmap)
    chip, = parts["chips"]
    assert set(chip["phases"]) == set(PHASES)
    assert all(v > 0 for v in chip["phases"].values())
    assert sum(chip["phases"].values()) + chip["unscoped_s"] == \
        pytest.approx(chip["busy_s"], rel=1e-9)
    (lo, hi), = [(s, e) for n, s, e in events["spans"]
                 if n == "bench.traced_call"]
    ops = events["chips"][0]
    for p, secs in chip["phases"].items():
        assert secs == pytest.approx(
            mask_s([o for o in ops if pmap.get(o[0]) == p], lo, hi))
    assert chip["unscoped_s"] == pytest.approx(
        mask_s([o for o in ops if o[0] not in pmap], lo, hi))
    assert chip["phases"]["sim.sm_phase"] > chip["busy_s"] / 2
    names = [n for n, _ in parts["unscoped_ops"]]
    assert names and not set(names) & set(pmap)


def test_split_takes_the_reducers_window_and_busy_time(recorded):
    events, pmap = recorded
    red = xtrace.reduce(events, "bench.traced_call")
    parts = phases.split(events, "bench.traced_call", pmap)
    assert [c["busy_s"] for c in parts["chips"]] == \
        pytest.approx([c["busy_s"] for c in red["chips"]], rel=1e-12)
    with pytest.raises(ValueError):
        phases.split(events, "bench.no_such_span", pmap)


HLO = """HloModule jit_run, is_scheduled=true

%fused_add (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(run)/sim.loop_control/while/body/sim.sm_phase/vmap()/add"}
}

%fused_bare (param_0: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  %negate.2 = s32[8]{0} negate(%param_0.1), metadata={op_name="jit(run)/while/body/sim.cta_issue/neg"}
  ROOT %scatter.3 = s32[8]{0} scatter(%negate.2, %negate.2, %negate.2), to_apply=%region
}

%mem_body (p: (s32[8])) -> (s32[8]) {
  %p = (s32[8]{0}) parameter(0)
  %gte = s32[8]{0} get-tuple-element(%p), index=0
  %copy.4 = s32[8]{0} copy(%gte)
  ROOT %tuple.5 = (s32[8]{0}) tuple(%copy.4)
}

ENTRY %main (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_add
  %fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_bare
  %tuple.6 = (s32[8]{0}) tuple(%fusion.2)
  %while.7 = (s32[8]{0}) while(%tuple.6), condition=%mem_cond, body=%mem_body, metadata={op_name="jit(run)/sim.mem_phase/while"}
  %gte.8 = s32[8]{0} get-tuple-element(%while.7), index=0
  ROOT %copy.9 = s32[8]{0} copy(%gte.8)
}
"""



def test_phase_map_takes_the_innermost_scope_and_fills_gaps():
    pmap = phases.phase_map(HLO)
    # the innermost sim.* segment of a fused root's op_name
    assert pmap["add.1"] == pmap["fusion.1"] == "sim.sm_phase"
    # a fusion whose root has no metadata: the phase its instructions name
    assert pmap["fusion.2"] == "sim.cta_issue"
    # what a phase's loop runs is that phase
    assert pmap["copy.4"] == pmap["while.7"] == "sim.mem_phase"
    assert "copy.9" not in pmap and "x" not in pmap


def test_hand_made_events_split_by_phase():
    pmap = phases.phase_map(HLO)
    ev = {"spans": [("bench.traced_call", 0, 100)],
          "chips": [[("fusion.1", 0, 30), ("fusion.2", 30, 40),
                     ("copy.4", 40, 45), ("copy.4", 50, 55),
                     ("copy.9", 60, 70), ("fusion.1", 90, 120),
                     ("fusion.1", 100, 110)]]}
    parts = phases.split(ev, "bench.traced_call", pmap)
    chip, = parts["chips"]
    assert chip["phases"] == pytest.approx({
        "sim.sm_phase": 40e-9, "sim.cta_issue": 10e-9,
        "sim.mem_phase": 10e-9})
    assert chip["unscoped_s"] == pytest.approx(10e-9)
    assert chip["busy_s"] == pytest.approx(70e-9)
    assert parts["unscoped_ops"] == [["copy.9", pytest.approx(10e-9)]]


def test_hand_made_overlap_counts_in_both_phases():
    pmap = phases.phase_map(HLO)
    ev = {"spans": [("bench.traced_call", 0, 100)],
          "chips": [[("fusion.1", 0, 30), ("copy.4", 20, 40)]]}
    chip, = phases.split(ev, "bench.traced_call", pmap)["chips"]
    assert chip["busy_s"] == pytest.approx(40e-9)
    assert sum(chip["phases"].values()) == pytest.approx(50e-9)


def record(trace=None) -> dict:
    return {"trace": trace, "setup_s": 1.0, "compile_s": 0.5,
            "window_s": 1.0, "winst": 1}


TRACE = {"quanta": 4, "window_s": 1.0, "busy_s": 0.5,
         "chips": [{"busy_s": 0.5, "n_ops": 9}]}


def read(name, run):
    return cells.metric_reader(name)(run)


@pytest.mark.parametrize("name", COMPILE_METRICS)
def test_compile_reader_gives_none_without_a_trace(name):
    assert read(name, record()) is None


@pytest.mark.parametrize("name", COMPILE_METRICS)
def test_compile_reader_gives_none_for_a_program_without_counters(
        name, monkeypatch):
    monkeypatch.delattr(telemetry, "compile_counters")
    assert read(name, record(trace=TRACE)) is None


def test_compile_readers_read_the_programs_counters():
    import jax
    jax.jit(lambda x: x * 5 - 2)(np.arange(3, dtype=np.int32))
    work = telemetry.compile_counters()
    assert work["trace_s"] > 0 and work["backend_compile_s"] > 0
    assert read("trace_lower_s", record(trace=TRACE)) == \
        work["trace_s"] + work["lower_s"]
    assert read("xla_compile_s", record(trace=TRACE)) == \
        work["backend_compile_s"]


def test_traced_run_reports_set_ups_compile_work(tmp_path, capsys,
                                                monkeypatch):
    """The counters the readers take at the end of a traced run hold
    set-up's work: nothing compiles from the window's end to the readers
    (nor in the window, ``test_phase_split_on_the_small_cell``)."""
    root = tinyroot.build(tmp_path)
    at_slice = []
    traced_slice = harness.traced_slice

    def snapshot_first(*args, **kwargs):
        at_slice.append(telemetry.compile_counters())
        return traced_slice(*args, **kwargs)

    monkeypatch.setattr(harness, "traced_slice", snapshot_first)
    rc = harness.run(tinyroot.args("tiny.solo", trace=1),
                     time.perf_counter(), root=root, allow_cpu=True)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    work, = at_slice
    assert metrics["trace_lower_s"] == work["trace_s"] + work["lower_s"] > 0
    assert metrics["xla_compile_s"] == work["backend_compile_s"] > 0


def test_phase_split_on_the_small_cell(tmp_path):
    root = tinyroot.build(tmp_path)
    split = cells.load_module(root / "bench" / "phase_split.py",
                              "bench_phase_split")
    out = split.measure("tiny.solo", 98765, root, time.perf_counter())
    assert out["quanta"] == 8
    assert set(out["ms_per_quantum"]) == set(PHASES)
    assert all(v > 0 for v in out["ms_per_quantum"].values())
    busy = out["device_ms_per_quantum"]
    assert busy > 0 and out["device_ops_per_quantum"] > 0
    # the CPU client may run two ops at once, a TPU core does not
    assert out["overlap_ms_per_quantum"] >= -1e-9 * busy
    assert 0 <= out["unscoped_share"] < 100
    assert out["setup_compile"]["backend_compile_n"] >= 1
    assert all(v == 0 for v in out["calls_compile"].values())
    assert out["untraced_call_s"] > 0 and out["traced_call_s"] > 0
    assert not (root / "bench" / "out" / "phase_split" / "tiny.solo").exists()
