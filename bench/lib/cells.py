"""Find what a cell names, by name: its entry in BENCHMARK.json, its
configuration file, its traffic file and the code of the traffic's kind.

Nothing here knows a particular cell. A configuration is
``bench/configs/<config>.json``, a traffic mix is
``bench/traffic/<traffic>.json`` naming a ``kind``, the kind's code is
``bench/kinds/<kind>.py`` and a per-layer metric's reader is
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# the scalar keys of a timing point (``DynConfig``'s); a run takes them
# from the configuration file, and no seed changes them
DYN_SCALARS = ("l1_hit_lat", "l2_lat", "part_lat", "dram_burst",
               "dram_row_penalty", "icnt_lat")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "configs" / f"{name}.json")


def traffic(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "traffic" / f"{name}.json")


def load_module(path: Path, name: str):
    if not path.is_file():
        raise KeyError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind(name: str, bench: Path = BENCH):
    return load_module(bench / "kinds" / f"{name}.py", f"bench_kind_{name}")


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read(run)`` function of a per-layer metric; ``run`` is the
    record of one traced run (see ``harness.Run``)."""
    safe = name.replace(".", "_").replace("-", "_")
    return load_module(bench / "metrics" / f"{name}.py",
                       f"bench_metric_{safe}").read


def default_point(cfg: dict) -> dict:
    """The configuration's own timing point, in the reference's form."""
    g = cfg["gpu"]
    point = {k: int(g[k]) for k in DYN_SCALARS}
    point.update(lat=list(g["lat_of_class"]), disp=list(g["disp_of_class"]),
                 sched=g["scheduler"])
    return point


def gpu_config(cfg: dict, point: dict):
    """The program's ``GPUConfig`` for one timing point."""
    from repro.sim.config import GPUConfig
    g = dict(cfg["gpu"])
    g.update({k: point[k] for k in DYN_SCALARS})
    g.update(lat_of_class=tuple(point["lat"]),
             disp_of_class=tuple(point["disp"]), scheduler=point["sched"])
    return GPUConfig(**g)


def workload(cfg: dict):
    """The program's ``Workload``, built from the data through the
    program's own ``build_kernel`` (so its generators move nothing here)."""
    from repro.sim.config import class_index
    from repro.sim.trace import Workload, build_kernel
    modes = cfg["address_modes"]
    w = Workload(cfg["workload"]["name"])
    for k in cfg["workload"]["kernels"]:
        body = [(class_index(c), bool(d), modes.index(m), int(p))
                for c, d, m, p in k["body"]]
        w.kernels.append(build_kernel(
            k["name"], n_ctas=int(k["n_ctas"]),
            warps_per_cta=int(k["warps_per_cta"]), body=body,
            repeats=int(k["repeats"])))
    return w
