#!/usr/bin/env python3
"""Split one cell's traced call by the program's phases and print the
split as the last line, one JSON object.

    python3 bench/phase_split.py --workload lavaMD.solo --seed 7

Set-up is a run's (bench/run.py): the cell's kind builds and compiles its
program, with the same compile cache. Then the call a ``--trace 1`` run
traces (the last ``trace_quanta`` quanta before the loop's horizon, from a
fresh state) runs once to warm up, once untraced and once traced. The
traced call's device time is divided by the ``sim.*`` named scope of each
op (``phases.split`` with the compiled program's HLO text). The line
gives the cell's device metrics of that call (``device_ms_per_quantum``
and the others, by their readers), and per quantum each phase's ms and
the unscoped ms, beside the unscoped share and the ops that hold it;
``overlap_ms_per_quantum`` is the phases plus unscoped time less busy, 0
where no two ops overlap.
It also gives the compile work of set-up and of the three calls, from the
program's counters, and the untraced and traced call's seconds: what
tracing costs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def hlo_text(runner) -> str:
    """The runner's compiled program as HLO text (the solo kind keeps it,
    compiled, as ``_run``)."""
    return runner._run.as_text()


def measure(workload: str, seed: int, root: Path, t_start: float) -> dict:
    import jax

    import cells
    import compile_work
    import harness
    import phases
    import program
    import xtrace

    bench = root / "bench"
    cell = cells.cell(workload, root)
    cfg = cells.config(cell["config"], bench)
    traffic = cells.traffic(cell["traffic"], bench)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    runner = cells.kind(traffic["kind"], bench).build(
        cfg, traffic, seed, devices)
    runner.compile()
    setup_s = time.perf_counter() - t_start
    work = [compile_work.counters()]

    n = traffic["trace_quanta"]
    harness.complete(runner, harness.short_state(runner, n)[0])
    state, _ = harness.short_state(runner, n)
    t = time.perf_counter()
    harness.complete(runner, state)
    untraced_s = time.perf_counter() - t
    state, start = harness.short_state(runner, n)
    trace_dir = bench / "out" / "phase_split" / workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        t = time.perf_counter()
        with harness.span(harness.TRACED_SPAN):
            out = harness.complete(runner, state)
        traced_s = time.perf_counter() - t
    finally:
        jax.profiler.stop_trace()
    work.append(compile_work.counters())
    quanta = program.quanta(start, out, runner.quantum)
    events = xtrace.load(str(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    red = xtrace.reduce(events, harness.TRACED_SPAN)
    parts = phases.split(events, harness.TRACED_SPAN,
                         phases.phase_map(hlo_text(runner)))

    chips = parts["chips"]
    k = len(chips)

    def ms(secs: float) -> float:
        return 1000 * secs / k / quanta

    by_phase = {p: ms(sum(c["phases"][p] for c in chips))
                for p in chips[0]["phases"]}
    unscoped = ms(sum(c["unscoped_s"] for c in chips))
    # the cell's device metrics, read from this call as a traced run would
    record = {"trace": dict(red, quanta=quanta)}
    device = {m: cells.metric_reader(m, bench)(record)
              for m in ("device_ms_per_quantum", "device_ops_per_quantum",
                        "device_idle_share")}
    busy = device["device_ms_per_quantum"]
    return {
        "workload": workload, "seed": seed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind},
        "quanta": quanta, **device,
        "ms_per_quantum": by_phase,
        "unscoped_ms_per_quantum": unscoped,
        "overlap_ms_per_quantum": sum(by_phase.values()) + unscoped - busy,
        "unscoped_share": 100 * unscoped / busy,
        "unscoped_ops": parts["unscoped_ops"],
        "setup_s": setup_s,
        "setup_compile": work[0],
        "calls_compile": None if None in work else {
            key: work[1][key] - work[0][key] for key in work[1]},
        "untraced_call_s": untraced_s,
        "traced_call_s": traced_s,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [str(BENCH / "lib"), str(BENCH.parent / "src")]
    print(json.dumps(measure(args.workload, args.seed, BENCH.parent,
                             T_START)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
