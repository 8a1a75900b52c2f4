"""Median megabytes (10**6 bytes) per window step that the snapshot store
sends to the device (the program's ``snapshot.h2d_bytes`` counter, per
step)."""

import progtrace


def read(ctx):
    b = progtrace.counter_p50(ctx, "snapshot.h2d_bytes")
    return b / 1e6 if b is not None else None
