"""A copy of the benchmark with a small cell added, for tests on the CPU:
the TINY widths of ``sim/config.py`` and a one-kernel workload of every
instruction class and address pattern, under the solo kind."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_GPU = dict(n_sm=8, warps_per_sm=8, n_subcores=2, max_cta_per_sm=16,
                l1_sets=16, l1_ways=4, l1_hit_lat=32, l2_slices=4,
                l2_sets=16, l2_ways=4, l2_lat=32, dram_channels=2,
                part_lat=8, dram_burst=4, dram_row_penalty=24,
                dram_row_div=64, icnt_lat=16, quantum=16, mshr_per_sm=8,
                addrset_cap=256, scheduler="gto", mem_blocks=1 << 22,
                telemetry_samples=0, telemetry_every=1,
                lat_of_class=[4, 4, 16, 8, 0, 0, 1],
                disp_of_class=[1, 1, 4, 2, 1, 1, 1])

KERNELS = [
    {"name": "calc", "n_ctas": 12, "warps_per_cta": 2, "repeats": 2,
     "body": [["fp32", True, "none", 0], ["fp32", False, "none", 0],
              ["sfu", True, "none", 0], ["ldg", False, "stream", 0],
              ["fp32", True, "none", 0], ["ldg", False, "random", 1],
              ["int32", True, "none", 0], ["bar", False, "none", 0],
              ["ldg", True, "strided", 2], ["tensor", True, "none", 0],
              ["stg", False, "stream", 7]]},
]


def tiny_config() -> dict:
    real = json.loads((BENCH / "configs" / "rtx3080ti.lavaMD.json")
                      .read_text())
    return dict(real, name="tiny", gpu=dict(TINY_GPU),
                workload={"name": "tiny", "kernels": KERNELS})


def build(tmp: Path) -> Path:
    """A root at ``tmp`` holding BENCHMARK.json and bench/ with the tiny
    cell ``tiny.solo`` added."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "out", ".jax_cache",
                                                  "__pycache__"))
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config()))
    t = json.loads((BENCH / "traffic" / "solo.json").read_text())
    t["trace_quanta"] = 8
    (tmp / "bench" / "traffic" / "tiny_solo.json").write_text(json.dumps(t))
    bj = json.loads((ROOT / "BENCHMARK.json").read_text())
    bj["workloads"].append({"name": "tiny.solo", "config": "tiny",
                            "traffic": "tiny_solo", "chips": 1,
                            "why": "test"})
    for m in bj["per_layer"]:
        if "workloads" in m and any(w.endswith(".solo")
                                    for w in m["workloads"]):
            m["workloads"].append("tiny.solo")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bj))
    return tmp


def args(workload: str, seed: int = 12345, seconds: float = 0.0,
         trace: int = 0):
    return type("Args", (), dict(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace))()
