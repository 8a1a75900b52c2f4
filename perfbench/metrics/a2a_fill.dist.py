"""Fill of the distributed DBQ's exchange (%): the distinct cold rows the
shards asked for (program counter ``dbq.cold_rows``) over the rows their
response buffers held (``dbq.req_slots``: S x S x R a fetch), summed over
the census tasks (``drive`` calls) that started inside the window. None
where the program counts neither (a one-chip engine, or a program
without these counters)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    if "window" not in ctx:
        return None
    lo, hi = ctx["window"]
    keys = {r.key for r in obs.records() if r.name == "drive"
            and lo <= r.t0_ns * 1e-9 <= hi}
    cold = slots = 0
    for k in keys:
        c = obs.counters(key=k)
        cold += c.get("dbq.cold_rows", 0)
        slots += c.get("dbq.req_slots", 0)
    return 100.0 * cold / slots if slots else None
