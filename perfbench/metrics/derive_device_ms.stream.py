"""Device milliseconds per traced time step of the snapshot store's
``derive`` (G'_t from the previous rows and the step's delta), by its
scope or else its program, from the profiler trace (progtrace.py)."""

import progtrace


def read(ctx):
    return progtrace.scope_ms_per_step(ctx, "derive")
