"""Device time of the distributed DBQ's all-to-all exchanges as a share of
the census window (%), per chip: the device seconds of the all-to-all
ops, summed over the chips that ran anything and over that many chips,
from the profiler trace. None where no such op ran (one chip)."""

from tracing import kernel_seconds_per_chip

#: the exchanges' own names in a v5e compile (``%all_to_all.19 = s32[4,1,
#: 8192] all-to-all(...)``: requests, then responses), and the op kind's
#: spelling should an instruction be named after it
A2A_OPS = ("all_to_all", "all-to-all")


def read(ctx):
    s = ctx.get("trace")
    if not s or s["window_s"] <= 0:
        return None
    k = kernel_seconds_per_chip(s, A2A_OPS)
    return 100.0 * k / s["window_s"] if k > 0 else None
