"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is the ``*.xplane.pb`` that ``jax.profiler`` writes. ``load``
turns it into plain events: per chip, the device operations as
(name, start_ns, end_ns); and the harness's host spans (TraceAnnotations
named ``bench.*``). ``reduce`` then works on those alone:

- busy: the union of a chip's operation intervals inside the window
  (control flow, whose event spans the operations it runs, is left out);
- idle share: 1 − busy / window;
- op count: the operations that start inside the window;
- top operations by time, and the longest idle gaps, each gap named by
  the innermost host span around its midpoint.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
# control flow: its event spans the operations it runs, so it is neither
# an operation of its own nor proof that the device was busy
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
CONTAINER_OP = re.compile(r"\s(while|conditional|call)\(")


def op_name(event_name: str) -> str | None:
    """Short name of a device operation ("fusion.12" of "%fusion.12 =
    s32[..] fusion(..)"), or None for a control-flow container."""
    short, _, text = event_name.lstrip("%").partition(" = ")
    if CONTAINER.match(short) or CONTAINER_OP.search(text):
        return None
    return short


def load(trace_dir: str) -> dict:
    """Device op events per chip and host spans of the newest trace under
    ``trace_dir``. Chips are the ``/device:TPU:<n>`` planes, their ops the
    "XLA Ops" line. A trace with no such plane (a CPU run) takes the ops
    that the CPU client ran, with their ``hlo_op`` stat, as one device."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    chips, spans, host_ops = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(op_name(e.name), int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events]
            chips[plane.name] = [o for o in ops if o[0] is not None]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    end = int(e.start_ns + e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns), end))
                    elif "hlo_op" in dict(e.stats) and op_name(e.name):
                        host_ops.append((op_name(e.name), int(e.start_ns),
                                         end))
    if not chips and host_ops:
        chips["/host:CPU"] = host_ops
    return {"chips": [sorted(chips[k]) for k in sorted(chips)],
            "spans": spans}


def union(intervals, lo: int, hi: int) -> list:
    """Merged intervals, clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def span_at(spans, t: float) -> str:
    """Name of the innermost (shortest) host span that holds time t."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no_span"


def reduce(events: dict, window: str, top: int = 10) -> dict:
    """Numbers of the traced window, the host span named ``window``."""
    wins = [(s, e) for n, s, e in events["spans"] if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in the trace")
    lo, hi = wins[-1]
    chips, by_name, gaps = [], {}, []
    for ops in events["chips"]:
        inside = [(n, s, e) for n, s, e in ops if lo <= s < hi]
        busy = union([(s, e) for _, s, e in inside], lo, hi)
        for n, s, e in inside:
            by_name[n] = by_name.get(n, 0) + (min(e, hi) - s)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((span_at(events["spans"], (s + e) / 2), e - s))
        chips.append({"busy_s": sum(e - s for s, e in busy) / 1e9,
                      "n_ops": len(inside)})
    n = max(len(chips), 1)
    window_s = (hi - lo) / 1e9
    busy_s = sum(c["busy_s"] for c in chips) / n
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "chips": chips,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, d / 1e9] for name, d in sorted(
            gaps, key=lambda g: -g[1])[:top]],
    }
