"""Drive whole runs of the small solo cell on the CPU (skipping the look
for a chip) and see ``correct`` hold for the sound program and fail for
each fault a one-chip solo cell can have: a step that returns its state
unchanged, half of the batch (the SMs the SM phase is vmapped over) left
out, and an answer altered where it is produced."""
import json
import time

import pytest

import cells
import harness
import program
import tinyroot

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.build(tmp_path_factory.mktemp("root"))


def result(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_cell(root, workload, trace=0):
    rc = harness.run(tinyroot.args(workload, trace=trace),
                     time.perf_counter(), root=root, allow_cpu=True)
    assert rc == 0


def plant(monkeypatch, wrap):
    """Every runner a kind builds is passed through ``wrap``."""
    real = cells.kind

    def kind(name, bench=cells.BENCH):
        mod = real(name, bench)
        return type("Planted", (), {"build": staticmethod(
            lambda *a: wrap(mod.build(*a)))})

    monkeypatch.setattr(cells, "kind", kind)


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_program_is_correct(root, capsys, trace):
    run_cell(root, "tiny.solo", trace)
    out = result(capsys)
    assert out["correct"] is True
    assert all(v["value"] == 0 for v in out["check"].values())
    assert list(out)[-1] == "check"


def test_state_returned_unchanged_is_not_correct(root, capsys, monkeypatch):
    def wrap(r):
        r.launch = lambda state: state
        return r
    plant(monkeypatch, wrap)
    run_cell(root, "tiny.solo")
    out = result(capsys)
    assert out["correct"] is False
    assert out["check"]["cycles"]["value"] > 0


def test_half_the_sms_left_out_is_not_correct(root, capsys, monkeypatch):
    """The per-SM counters of half of the SMs come back empty, as if the
    vmapped SM phase had run over the other half alone."""
    def wrap(r):
        launch = r.launch

        def half(state):
            out = launch(state)
            stats = {k: v.at[v.shape[0] // 2:].set(0)
                     for k, v in out["stats_sm"].items()}
            return dict(out, stats_sm=stats)
        r.launch = half
        return r
    plant(monkeypatch, wrap)
    run_cell(root, "tiny.solo")
    out = result(capsys)
    assert out["correct"] is False
    assert out["check"]["issued"]["value"] > 0
    assert out["check"]["every_lane_issued"]["value"] > 0


def test_altered_answer_is_not_correct(root, capsys, monkeypatch):
    real = program.lane_stats

    def altered(*a):
        stats = real(*a)
        stats[0]["l2_hit"] += 1
        return stats
    monkeypatch.setattr(program, "lane_stats", altered)
    run_cell(root, "tiny.solo")
    out = result(capsys)
    assert out["correct"] is False
    assert out["check"]["l2_hit"]["value"] == 1
