"""Figs. 13-14 reproduction: machine scalability.

Per-shard work / communication as the shard count grows (the structural
analogue of the paper's wall-clock speedup curves — on one CPU we report
the quantities that determine speedup: max per-shard work, total remote
rows, skew with/without rebalancing, and hot-row cache effect)."""

from __future__ import annotations

import os
import subprocess
import sys

from .common import Table

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = """
import json, numpy as np
from repro.core.pattern import get_pattern
from repro.core.plangen import generate_best_plan
from repro.core.engine_dist import enumerate_distributed
from repro.graph.generate import powerlaw
g = powerlaw(300, 4, seed=6)
P = get_pattern("chordal-square")
plan = generate_best_plan(P, g.stats())
out = []
for hot, reb in ((0, False), (32, False), (32, True)):
    st = enumerate_distributed(plan, g, batch_per_shard=32, hot=hot,
                               rebalance=reb)
    lv = st.per_shard_level_sizes
    out.append(dict(hot=hot, reb=reb, count=st.count,
                    cold=st.cold_rows_fetched,
                    max_work=int(lv[-1].max()) if len(lv) else 0,
                    min_work=int(lv[-1].min()) if len(lv) else 0))
print(json.dumps(out))
"""


def run() -> Table:
    t = Table("Figs. 13-14: scalability drivers vs shard count",
              ["shards", "hot", "rebalance", "matches", "remote rows",
               "final-level max/min work"])
    for shards in (2, 4, 8):
        env = dict(os.environ)
        # virtual host meshes: the children stay off the chip, which a
        # parent process that has touched JAX holds
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={shards}"
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        res = subprocess.run([sys.executable, "-c", _CODE],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        if res.returncode != 0:
            raise RuntimeError(res.stderr[-2000:])
        import json
        for r in json.loads(res.stdout.strip().splitlines()[-1]):
            t.add(shards, r["hot"], r["reb"], r["count"], r["cold"],
                  f"{r['max_work']}/{r['min_work']}")
    return t


if __name__ == "__main__":
    run().show()
