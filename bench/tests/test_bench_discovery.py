"""A configuration, a traffic mix, a traffic kind or a per-layer metric is
found by its name once its file exists, with no edit to any other file."""
import json

import pytest

import cells
import tinyroot


@pytest.fixture()
def bench(tmp_path):
    return tinyroot.build(tmp_path) / "bench"


def test_new_configuration_file_is_found(bench):
    cfg = cells.config("tiny", bench)
    assert cfg["gpu"]["n_sm"] == 8
    assert len(cells.workload(cfg).kernels) == 1


def test_new_traffic_and_kind_files_are_found(bench):
    (bench / "kinds" / "echo.py").write_text(
        "def build(cfg, traffic, seed, devices):\n"
        "    return (traffic['value'], seed)\n")
    (bench / "traffic" / "echo_mix.json").write_text(
        json.dumps({"kind": "echo", "value": 7}))
    traffic = cells.traffic("echo_mix", bench)
    assert cells.kind(traffic["kind"], bench).build(None, traffic, 3,
                                                    []) == (7, 3)


def test_new_metric_reader_is_found(bench):
    (bench / "metrics" / "calls.per_window.py").write_text(
        "def read(run):\n    return run['calls']\n")
    assert cells.metric_reader("calls.per_window", bench)({"calls": 3}) == 3


def test_missing_name_is_an_error(bench):
    with pytest.raises(KeyError):
        cells.kind("no_such_kind", bench)
    with pytest.raises(FileNotFoundError):
        cells.config("no_such_config", bench)
