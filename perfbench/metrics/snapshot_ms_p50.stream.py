"""Median milliseconds of the per-step snapshot advance (the backend's
per-step prepare, which derives G'_t on the device, waited for), from the
benchmark's ``snapshot`` span over the window's steps."""

import statistics


def read(ctx):
    lo, hi = ctx["window"]
    ms = [(b - a) * 1e3 for n, a, b in ctx["spans"].records
          if n == "snapshot" and lo <= a and b <= hi]
    return statistics.median(ms) if ms else None
