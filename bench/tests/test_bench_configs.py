"""The configuration files hold the RTX 3080 Ti model at its published
widths and the applications' kernels as the program generates them, at
the size the configuration's source gives."""
import dataclasses
import json

import numpy as np
import pytest

import cells
from repro.sim import config as C
from repro.sim import trace as T
from repro.workloads.synthetic import make_workload

CONFIGS = {"rtx3080ti.lavaMD": "lavaMD"}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_widths_are_the_rtx3080ti_model(name):
    cfg = cells.config(name)
    want = {f.name: getattr(C.RTX3080TI, f.name)
            for f in dataclasses.fields(C.RTX3080TI)}
    got = dict(cfg["gpu"], lat_of_class=tuple(cfg["gpu"]["lat_of_class"]),
               disp_of_class=tuple(cfg["gpu"]["disp_of_class"]))
    assert got == want
    assert cells.gpu_config(cfg, cells.default_point(cfg)) == C.RTX3080TI


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tables_match_the_program(name):
    cfg = cells.config(name)
    assert tuple(cfg["classes"]) == C.CLASS_NAMES
    assert tuple(cfg["unit_of_class"]) == C.UNIT_OF_CLASS
    modes = cfg["address_modes"]
    assert [modes.index(m) for m in ("none", "stream", "strided", "random")] \
        == [T.A_NONE, T.A_STREAM, T.A_STRIDED, T.A_RANDOM]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_kernels_are_the_program_workload(name):
    """Each kernel is the program's own, with the configuration's CTA
    count in place of the generator's."""
    cfg = cells.config(name)
    got = cells.workload(cfg)
    want = make_workload(CONFIGS[name], 1.0)
    assert len(got.kernels) == len(want.kernels)
    for g, w in zip(got.kernels, want.kernels):
        assert g == dataclasses.replace(w, n_ctas=g.n_ctas)


def test_lavamd_is_rodinias_default_input():
    """Rodinia's lavaMD with ``-boxes1d 10`` launches one CTA of 128
    threads per box: 10**3 CTAs of 4 warps."""
    k, = cells.config("rtx3080ti.lavaMD")["workload"]["kernels"]
    assert k["n_ctas"] == 10 ** 3
    assert k["warps_per_cta"] * 32 == 128


def test_benchmark_json_names_files_that_exist():
    bj = cells.benchmark()
    assert bj["paths"] == ["bench"]
    for c in bj["configs"]:
        assert cells.load_json(cells.ROOT / c["file"])["name"] == c["name"]
    for w in bj["workloads"]:
        cells.config(w["config"])
        cells.kind(cells.traffic(w["traffic"])["kind"])
    for m in bj["end_to_end"] + bj["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
    assert np.all([len(json.dumps(w["why"])) <= 202 for w in bj["workloads"]])
