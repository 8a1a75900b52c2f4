"""Share of the time the host spends inside the program's time steps
(``repro.timestep`` spans) in which no op ran on the device (%): the idle
inside a step, without the idle between steps that the open-loop
schedule leaves by design. From the profiler trace (progtrace.py)."""

import progtrace


def read(ctx):
    rec = progtrace.trace_record(ctx)
    if rec is None:
        return None
    steps = progtrace.host_spans(rec, "timestep")
    total = sum(b - a for a, b in steps)
    if not total:
        return None
    busy = progtrace.busy_union(rec)
    idle = sum(b - a - progtrace.covered(busy, a, b) for a, b in steps)
    return 100.0 * idle / total
