"""Median milliseconds per window step that the snapshot store spends
placing the step's buffers on the device (the program's
``snapshot.place`` spans, summed per step)."""

import progtrace


def read(ctx):
    return progtrace.span_ms_p50(ctx, "snapshot.place")
