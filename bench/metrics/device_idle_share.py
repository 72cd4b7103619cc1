"""Percent of the traced call in which no operation ran on the device:
1 − (union of device op intervals) / (traced window), mean over chips."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["chips"]:
        return None
    idle = [1 - c["busy_s"] / tr["window_s"] for c in tr["chips"]]
    return 100 * sum(idle) / len(idle)
