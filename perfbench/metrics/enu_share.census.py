"""Device time of ENU compaction as a share of the census window (%):
device ops under the program's ``enu`` scope (engine_jax._expand), from
the profiler trace (progtrace.py)."""

import progtrace


def read(ctx):
    return progtrace.scope_share(ctx, "enu")
