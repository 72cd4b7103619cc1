"""Simulated warp-instructions of every lane of every call in the window,
over the window's host seconds (tracing off)."""


def read(run):
    return run["winst"] / run["window_s"]
