"""Median milliseconds per step of delta enumeration (the ΔP_i plans'
dispatches, each ending when its counts reach the host), from the
benchmark's ``chunk`` spans summed within each window step."""

import statistics


def read(ctx):
    ms = [x for x in ctx.get("delta_enum_ms", []) if x > 0]
    return statistics.median(ms) if ms else None
