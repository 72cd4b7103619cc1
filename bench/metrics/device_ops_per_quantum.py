"""Device operations that start in the traced call, per quantum, mean
over chips."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["quanta"] or not tr["chips"]:
        return None
    ops = sum(c["n_ops"] for c in tr["chips"]) / len(tr["chips"])
    return ops / tr["quanta"]
