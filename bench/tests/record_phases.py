"""Record the phase fixture of ``test_bench_phases.py`` on the CPU: a
traced one-quantum call of the small solo cell (``tinyroot``) and its
compiled program's HLO text, with the stack-frame tables left out. The
process keeps to one CPU core, so the CPU client runs one op at a time,
as a TPU core does, and no two ops overlap in the trace.

    PYTHONPATH=src JAX_PLATFORMS=cpu python bench/tests/record_phases.py
"""
import gzip
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "lib"), str(HERE.parents[1] / "src"),
                str(HERE), str(HERE.parent)]

os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})

import jax  # noqa: E402

import cells  # noqa: E402
import harness  # noqa: E402
import tinyroot  # noqa: E402
from phase_split import hlo_text  # noqa: E402

OUT = HERE / "phase_data"
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def main() -> None:
    root = tinyroot.build(Path(tempfile.mkdtemp()))
    bench = root / "bench"
    cfg = cells.config("tiny", bench)
    runner = cells.kind("solo", bench).build(
        cfg, cells.traffic("tiny_solo", bench), 1, jax.devices())
    runner.compile()
    harness.complete(runner, runner.init())
    state, _ = harness.short_state(runner, 1)
    harness.complete(runner, state)
    state, _ = harness.short_state(runner, 1)
    trace = Path(tempfile.mkdtemp())
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # no Python frames, ops and spans
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace), profiler_options=options)
    with harness.span(harness.TRACED_SPAN):
        harness.complete(runner, state)
    jax.profiler.stop_trace()
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    pb, = trace.glob("**/*.xplane.pb")
    shutil.copy(pb, OUT / "cpu_trace.xplane.pb")
    blocks = hlo_text(runner).split("\n\n")
    text = "\n\n".join(b for b in blocks if b.split("\n")[0] not in TABLES)
    with gzip.open(OUT / "program.hlo.gz", "wt") as f:
        f.write(text)


if __name__ == "__main__":
    main()
