"""Device busy milliseconds per quantum in the traced call, mean over
chips. The call's quanta are counted from its final state (for a batch of
lanes: the slowest lane's, which the vmapped loop runs to)."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["quanta"]:
        return None
    return 1000 * tr["busy_s"] / tr["quanta"]
