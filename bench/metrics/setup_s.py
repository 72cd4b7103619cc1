"""Host seconds from process start to the first timed call: JAX start-up,
trace build, compile or cache load, and placement."""


def read(run):
    return run["setup_s"]
