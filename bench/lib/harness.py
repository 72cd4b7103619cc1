"""One run of one cell: set-up, the measured window, an optional traced
slice, the output check, and the result line.

Set-up runs from process start to the first timed call: JAX start-up,
building the traces and the program, lowering and compiling it (or
loading it from the persistent cache) and placing its inputs. The window
then calls the compiled program back to back, each call a whole
simulation (of every lane the kind runs) from a fresh initial state to
its final state, until ``--seconds`` have passed; it ends when the last
call has finished.

With ``--trace 1`` the run also traces a shorter call of the same program
(the clock starts ``trace_quanta`` quanta before the loop's horizon, so
the call runs exactly that many) and reports the cell's per-layer metrics
in place of the end-to-end ones. The traced window is that call's
dispatch and wait alone: its initial state is made before the trace
starts.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

import cells
import check
import program
import xtrace

TRACED_SPAN = "bench.traced_call"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def complete(runner, state):
    """One whole call: dispatch, then wait for the final state."""
    import jax
    with span("bench.dispatch"):
        out = runner.launch(state)
    with span("bench.block_until_ready"):
        return jax.block_until_ready(out)


def short_state(runner, n_quanta: int) -> tuple:
    """An initial state whose clock starts ``n_quanta`` quanta before the
    loop's horizon, ready on the device, and that start cycle."""
    import jax
    start = program.MAX_CYCLES - n_quanta * runner.quantum
    state = program.with_cycle(runner.init(), start)
    return jax.block_until_ready(state), start


def traced_slice(runner, n_quanta: int, trace_dir: Path) -> dict:
    import jax
    state, start = short_state(runner, n_quanta)
    complete(runner, state)                   # warm: nothing new to load
    state, start = short_state(runner, n_quanta)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with span(TRACED_SPAN):
            out = complete(runner, state)
    finally:
        jax.profiler.stop_trace()
    quanta = program.quanta(start, out, runner.quantum)
    red = xtrace.reduce(xtrace.load(str(trace_dir)), TRACED_SPAN)
    shutil.rmtree(trace_dir, ignore_errors=True)
    red["quanta"] = quanta
    return red


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(args, t_start: float, root: Path = cells.ROOT,
        allow_cpu: bool = False) -> int:
    """Run ``args.workload`` once; print the result line. Returns the
    exit code. ``allow_cpu`` lets a test drive a run without a chip."""
    bench = root / "bench"
    bj = cells.benchmark(root)
    cell = next((w for w in bj["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    cfg = cells.config(cell["config"], bench)
    traffic = cells.traffic(cell["traffic"], bench)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    platform = devices[0].platform
    if not allow_cpu and (platform == "cpu" or len(devices) < cell["chips"]):
        log(f"{args.workload} needs {cell['chips']} accelerator chip(s); "
            f"JAX finds {len(devices)} {platform} device(s)")
        return 3

    with span("bench.build"):
        runner = cells.kind(traffic["kind"], bench).build(
            cfg, traffic, args.seed, devices)
    t = time.perf_counter()
    with span("bench.compile"):
        runner.compile()
    compile_s = time.perf_counter() - t

    w0 = time.perf_counter()
    setup_s = w0 - t_start
    outs, ends = [], []
    while not outs or time.perf_counter() - w0 < args.seconds:
        outs.append(complete(runner, runner.init()))
        ends.append(time.perf_counter() - w0)
    window_s = ends[-1]
    log(f"window: {len(outs)} call(s) ending at {ends} s "
        f"(set-up {setup_s:.3f} s, compile {compile_s:.3f} s)")
    peak = memory_peak(runner.devices)

    red = None
    if args.trace:
        red = traced_slice(runner, traffic["trace_quanta"],
                           bench / "out" / "trace" / args.workload)
        log(f"traced {red['quanta']} quanta: busy {red['busy_s']:.6f} s "
            f"of {red['window_s']:.6f} s")

    with span("bench.stats"):
        n = len(runner.points)
        stats = [program.lane_stats(o, n, runner.batched) for o in outs]
    del outs
    lanes = runner.sample(np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(args.seed), 1]))), stats[0])
    t = time.perf_counter()
    with span("bench.check"):
        ref = check.reference_stats(cfg, [runner.points[i] for i in lanes],
                                    program.MAX_CYCLES)
        gap = check.gaps(cfg, stats, lanes, ref)
    correct, numbers = check.verdict(gap)
    log(f"reference: lanes {lanes} in {time.perf_counter() - t:.3f} s")

    record = {
        "cell": cell, "cell_config": cfg, "traffic": traffic,
        "n_chips": len(runner.devices),
        "setup_s": setup_s, "compile_s": compile_s, "window_s": window_s,
        "calls": len(stats), "lane_stats": stats[0],
        "winst": sum(s["issued"] for call in stats for s in call),
        "trace": red,
    }
    wanted = bj["per_layer"] if args.trace else bj["end_to_end"]
    metrics = {}
    for m in wanted:
        if applies(m, cell["name"]):
            value = cells.metric_reader(m["name"], bench)(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {
        "correct": correct,
        "attempted": sum(len(call) for call in stats),
        "failed": sum(s["timeouts"] > 0 for call in stats for s in call),
        "metrics": metrics, "device": device,
    }
    if red is not None:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["check"] = numbers
    for k, v in numbers.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
