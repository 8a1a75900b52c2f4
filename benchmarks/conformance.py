"""Cross-engine conformance: every backend of the unified Executor API
must produce identical match counts on the same plan (the correctness bar
set by the distributed-subgraph-matching survey — exact agreement, not
approximate). The driver's splitting/overflow policy is shared, so any
disagreement is an engine bug, never a chunking artifact."""

from __future__ import annotations

import jax

from repro.core.executor import make_executor
from repro.core.pattern import get_pattern
from repro.core.plangen import generate_best_plan
from repro.graph.generate import powerlaw

from .common import Table

PATTERNS = ("triangle", "square", "clique4", "house")


def run() -> Table:
    g = powerlaw(150, 4, seed=7)
    gi_impl = "interpret" if jax.default_backend() == "cpu" else "auto"
    t = Table("Cross-engine conformance (unified Executor API)",
              ["pattern", "ref", "jax", "jax-gpu", "oocache", "ooc hit %",
               "agree"])
    for pname in PATTERNS:
        p = get_pattern(pname)
        plan = generate_best_plan(p, g.stats())
        ref = make_executor("ref").run(plan, g, batch=64)
        jx = make_executor("jax").run(plan, g, batch=64)
        # fused gather+intersect fetch path: the compiled kernel on a
        # chip, interpret mode on CPU so the real kernel code still runs
        gpu = make_executor("jax-gpu",
                            gather_intersect_impl=gi_impl).run(
                                plan, g, batch=64)
        # whole device footprint (slab + staging + hot + sentinel)
        # bounded below 25% of the graph's rows, like the tests
        ooc = make_executor("oocache", cache_rows=int(g.n * 0.12),
                            hot=int(g.n * 0.04)).run(plan, g, batch=64)
        agree = ref.count == jx.count == gpu.count == ooc.count
        t.add(pname, ref.count, jx.count, gpu.count, ooc.count,
              f"{ooc.extras['cache']['hit_rate'] * 100:.1f}",
              "yes" if agree else "NO")
    return t


if __name__ == "__main__":
    run().show()
