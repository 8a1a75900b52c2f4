"""Device time of the Pallas INT kernels (plain and fused intersect) as a
share of the census window (%), per chip: their device seconds summed
over the chips that ran anything, over that many chips, from the profiler
trace."""

from tracing import kernel_seconds_per_chip

#: the two kernels' ops: sorted_intersect_pallas.N, gather_intersect_pallas.N
INT_KERNELS = ("intersect_pallas",)


def read(ctx):
    s = ctx.get("trace")
    if not s or s["window_s"] <= 0:
        return None
    k = kernel_seconds_per_chip(s, INT_KERNELS)
    return 100.0 * k / s["window_s"] if k > 0 else None
