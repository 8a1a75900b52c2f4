"""Share of the census window in which no op ran on the device (%):
1 - (union of device op intervals) / window, from the profiler trace."""


def read(ctx):
    s = ctx.get("trace")
    if not s or s["window_s"] <= 0 or s["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
