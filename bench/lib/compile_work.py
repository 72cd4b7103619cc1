"""What the compile metrics read: the program's process-wide compile
counters (``repro.core.telemetry.compile_counters``). They count from the
import of the program's telemetry module, which the engine imports before
a kind builds its program. In a traced run, where they are read, they hold
set-up's compile work alone: neither the timed window nor the traced
slice compiles anything (the eager op that sets a traced call's clock,
``program.with_cycle``, was compiled in set-up already)."""


def counters() -> dict | None:
    """The program's compile counters now, or None where the program has
    none."""
    from repro.core import telemetry
    snapshot = getattr(telemetry, "compile_counters", None)
    return snapshot() if snapshot else None
