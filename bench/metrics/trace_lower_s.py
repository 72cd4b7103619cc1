"""Host seconds spent tracing functions to jaxprs and lowering them to
MLIR modules, from the program's compile counters (``compile_work``):
set-up's, in a traced run. None without a trace, or where the program
has no such counters."""
from compile_work import counters


def read(run):
    work = counters() if run["trace"] else None
    if work is None:
        return None
    return work["trace_s"] + work["lower_s"]
