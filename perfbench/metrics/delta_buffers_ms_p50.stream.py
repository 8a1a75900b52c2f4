"""Median milliseconds per window step that the snapshot store spends
building the host delta buffers (the program's
``snapshot.delta_buffers`` spans, both directions, summed per step)."""

import progtrace


def read(ctx):
    return progtrace.span_ms_p50(ctx, "snapshot.delta_buffers")
