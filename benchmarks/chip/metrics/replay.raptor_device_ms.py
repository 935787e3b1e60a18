"""Device milliseconds of one call of the raptor replay engine: the
engine's device time in the traced part of the window over its runs
there.

The engine is the executable that ``jax.jit(jax.vmap(trial))`` builds in
``repro.sim.vector_queue._raptor_runner``: every trial of one call
booked through ``repro.sim.scan_core.blocked_event_replay``.  It is
found in the trace by the name the program gives it.
"""
from trace import module_seconds

EXECUTABLE = "jit_trial"


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    sec, runs = module_seconds(t, EXECUTABLE)
    if not runs:
        return None
    return 1000.0 * sec / runs
