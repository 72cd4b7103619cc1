"""Host seconds in the backend compile, from the program's compile
counters (``compile_work``): set-up's, in a traced run. JAX's
backend-compile span holds the persistent-cache lookup, so a program
loaded from the cache counts its retrieval here. None without a trace, or
where the program has no such counters."""
from compile_work import counters


def read(run):
    work = counters() if run["trace"] else None
    if work is None:
        return None
    return work["backend_compile_s"]
