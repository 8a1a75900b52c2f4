"""The INT kernels' share of their roofline (%): the least bytes the
triangle plan's intersections read for the tasks run (workmodel.py, from
real adjacency lengths) over HBM bandwidth times the kernels' device time.
Bytes bound it: a merge makes about one compare per entry, far under the
chip's integer peak. The kernels' seconds are summed over the chips, not
taken per chip: on a mesh the least bytes are spread over all of them,
so the roofline is the HBM bandwidth times the aggregate chip-seconds."""

from tracing import kernel_seconds

#: the two kernels' ops: sorted_intersect_pallas.N, gather_intersect_pallas.N
INT_KERNELS = ("intersect_pallas",)


def read(ctx):
    s, least = ctx.get("trace"), ctx.get("least_int_bytes")
    if not s or not least:
        return None
    k = kernel_seconds(s, INT_KERNELS)
    if k <= 0:
        return None
    return 100.0 * least / (ctx["peaks"]["hbm_bytes_per_s"] * k)
