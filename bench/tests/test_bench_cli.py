"""``bench/run.py`` refuses to run without an accelerator, and without the
program beside it, and then prints no result line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def run_py(root: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lavaMD.solo",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def no_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_cpu_backend_exits_nonzero_without_result():
    p = run_py(ROOT)
    assert p.returncode != 0
    assert no_result_line(p.stdout)
    assert "accelerator" in p.stderr


DRIVE_WITHOUT_CHIP_CHECK = """
import sys, time
from pathlib import Path
sys.path[:0] = ["bench/lib"]
import harness
args = type("A", (), dict(workload="lavaMD.solo", seed=5, seconds=1.0,
                          trace=int(sys.argv[1])))()
sys.exit(harness.run(args, time.perf_counter(), root=Path("."),
                     allow_cpu=True))
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_bench_files_alone_exit_nonzero_without_result(tmp_path, trace):
    """Only BENCHMARK.json and bench/: the program is missing, so the run
    fails once past the look for a chip (which this run skips)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    p = subprocess.run(
        [sys.executable, "-c", DRIVE_WITHOUT_CHIP_CHECK, str(trace)],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert no_result_line(p.stdout)
