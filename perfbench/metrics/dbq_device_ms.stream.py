"""Device milliseconds per traced time step under the program's ``dbq``
scope (the typed row fetches and the prev/cur stacking of the delta
enumeration), from the profiler trace (progtrace.py)."""

import progtrace


def read(ctx):
    return progtrace.scope_ms_per_step(ctx, "dbq")
