"""Host seconds of lowering and compiling the cell's programs (or loading
them from the persistent cache) inside set-up."""


def read(run):
    return run["compile_s"]
