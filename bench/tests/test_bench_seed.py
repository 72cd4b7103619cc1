"""A seed changes inputs only: the SM labels of the initial state. It
never changes a timing value, a shape, or the compiled program, and every
relabeling gives the same statistics, so every seed simulates the same
input."""
import jax
import numpy as np
import pytest

import cells
import program
import tinyroot

SEEDS = (3, 2**31 + 11)
PAIRS = [SEEDS, (0, 2**32 + 5)]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tinyroot.build(tmp_path_factory.mktemp("root")) / "bench"


def runners(bench, seeds):
    cfg = cells.config("tiny", bench)
    t = cells.traffic("tiny_solo", bench)
    kind = cells.kind(t["kind"], bench)
    return [kind.build(cfg, t, s, jax.devices()) for s in seeds]


@pytest.mark.parametrize("seeds", PAIRS)
def test_seed_never_changes_the_program(bench, seeds):
    a, b = runners(bench, seeds)
    ta = a._run.lower(a.init(), *a.args()).as_text()
    tb = b._run.lower(b.init(), *b.args()).as_text()
    assert ta == tb
    sa, sb = a.init(), b.init()
    assert jax.tree_util.tree_structure(sa) == jax.tree_util.tree_structure(sb)
    assert [x.shape for x in jax.tree_util.tree_leaves(sa)] == \
        [x.shape for x in jax.tree_util.tree_leaves(sb)]


def test_seed_keeps_the_configuration_timing_point(bench):
    cfg = cells.config("tiny", bench)
    for r in runners(bench, SEEDS):
        assert r.points == [cells.default_point(cfg)]


def test_solo_seed_relabels_the_sms_only():
    cfg = cells.config("rtx3080ti.lavaMD")
    la, lb = (program.sm_labels(cfg["gpu"]["n_sm"], s) for s in SEEDS)
    assert sorted(la) == sorted(lb) == list(range(cfg["gpu"]["n_sm"]))
    assert not np.array_equal(la, lb)
    np.testing.assert_array_equal(la, program.sm_labels(80, SEEDS[0]))
