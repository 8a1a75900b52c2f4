"""Device time of ENU compaction as a share of the census window (%), per
chip: device ops under the program's ``enu`` scope (engine_jax._expand),
summed over the chips that ran anything and over that many chips, from
the profiler trace (progtrace.py)."""

import progtrace


def read(ctx):
    return progtrace.scope_share(ctx, "enu")
