"""The ``dist`` backend as a census runs it, on four forced CPU devices
(one subprocess; the main pytest process keeps one device): starts dealt
round-robin over the shards, rows placed shard by shard in the whole
table's layout, and the backend's spans and DBQ counters in ``repro.obs``.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import json
import numpy as np, jax
from repro import obs
from repro.core.engine_dist import enumeration_mesh
from repro.core.executor import (DistBackend, ExecutorConfig, RefBackend,
                                 drive)
from repro.core.pattern import get_pattern
from repro.core.plangen import generate_best_plan
from repro.distributed.rowstore import build_row_shards
from repro.graph.generate import powerlaw
from repro.graph.storage import pad_rows

S, B = 4, 512
g = powerlaw(3001, 6, seed=2)      # n + 1 rows do not divide by S: padding
plan = generate_best_plan(get_pattern("triangle"), g.stats())
# a stratified task: start j drawn from degree band j (ids rank degree)
rng = np.random.default_rng(0)
bands = np.arange(g.n)[:(g.n // B) * B].reshape(B, -1)
task = bands[np.arange(B), rng.integers(0, bands.shape[1], B)]
task = task.astype(np.int32)
out = {}


def one_task(cls):
    class Task(cls):
        def start_batches(self, config):
            yield task, np.ones(B, bool)
    return Task


mesh = enumeration_mesh(devices=jax.devices()[:S])
out["ref"] = drive(one_task(RefBackend)(), plan, g,
                   ExecutorConfig(batch=B)).count
obs.reset()
b = one_task(DistBackend)(mesh=mesh, hot=32, req_cap=512)
st = drive(b, plan, g, ExecutorConfig(batch=B, caps=(8192, 8192)))
fdeg = np.array([int((a > v).sum()) for v, a in enumerate(g.adj)])
out.update(
    count=st.count, splits=st.chunks_split, retries=st.chunks_retried,
    per_shard_counts=st.extras["per_shard_counts"].tolist(),
    levels=st.extras["per_shard_level_sizes"].tolist(),
    dealt_level1=[int(fdeg[task[s::S]].sum()) for s in range(S)],
    deal=b.deal(np.arange(8)).tolist(),
    cold=st.extras["cold_rows_fetched"], counters=obs.counters(),
    spans=sorted({r.name for r in obs.records()}),
    pack_spans=sum(r.name == "rowstore.pack" for r in obs.records()))

# the placed rows against the whole-table layout, built as it always was
for hot in (0, 32):
    pb = DistBackend(mesh=mesh, hot=hot)
    pb.prepare(plan, g, ExecutorConfig(batch=B))
    n = g.n
    rps = -(-(n + 1) // S)
    empty = np.zeros(0, np.int64)
    rows = pad_rows(list(g.adj) + [empty] * (S * rps - n), n, lane=128)
    whole = rows.reshape(S, rps, rows.shape[1])
    hot_rows = rows[n - hot:n + 1] if hot else rows[n:n + 1]
    legacy = build_row_shards(g, S, hot=hot)
    out[f"placed_{hot}"] = dict(
        shards=bool(np.array_equal(np.asarray(pb.shards), whole)),
        hot=bool(np.array_equal(np.asarray(pb.hot_rows), hot_rows)),
        legacy=bool(np.array_equal(legacy[0], whole)
                    and np.array_equal(legacy[1], hot_rows)),
        on_own_device=all(
            np.array_equal(np.asarray(sh.data)[0],
                           whole[sh.index[0].start])
            and sh.device == mesh.devices[sh.index[0].start]
            for sh in pb.shards.addressable_shards),
        padding_is_sentinel=bool((whole[-1, -(S * rps - n):] == n).all()))

# a request budget too small for the task: drops, escalation, exact count
obs.reset()
small = one_task(DistBackend)(mesh=mesh, hot=0, req_cap=8)
st = drive(small, plan, g, ExecutorConfig(batch=B, caps=(8192, 8192)))
out["small"] = dict(count=st.count, drops=st.drops_seen,
                    req_cap=small.req_cap, counters=obs.counters())
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_dealt_stratified_task_counts_exactly_with_balanced_shards(dist):
    assert dist["deal"] == [0, 4, 1, 5, 2, 6, 3, 7]
    assert dist["count"] == dist["ref"] > 0
    assert sum(dist["per_shard_counts"]) == dist["count"]
    assert dist["splits"] == dist["retries"] == 0
    level1 = dist["levels"][0]
    # shard s ran starts s, s + S, ...: its level-1 rows are their
    # forward neighbours
    assert level1 == dist["dealt_level1"]
    assert max(level1) <= 1.1 * min(level1)


@pytest.mark.parametrize("hot", [0, 32])
def test_rows_placed_shard_by_shard_equal_the_whole_table(dist, hot):
    placed = dist[f"placed_{hot}"]
    assert placed == dict(shards=True, hot=True, legacy=True,
                          on_own_device=True, padding_is_sentinel=True)


def test_dbq_counters_after_one_drive(dist):
    c = dist["counters"]
    assert c["dbq.cold_rows"] == dist["cold"] > 0
    assert 0 < c["dbq.cold_rows"] / c["dbq.req_slots"] <= 1
    # two fetches in the triangle plan, [S, R] response rows each, S shards
    assert c["dbq.req_slots"] == 2 * 4 * 4 * 512
    assert "dbq.req_drops" not in c and "chunks.req_escalated" not in c
    assert {"prepare", "rowstore.pack", "rowstore.place", "chunk",
            "chunk.dispatch", "chunk.wait", "chunk.decode",
            "jit.build"} <= set(dist["spans"])
    assert dist["pack_spans"] == 4


def test_small_request_budget_counts_drops_and_escalations(dist):
    small = dist["small"]
    assert small["count"] == dist["ref"]
    c = small["counters"]
    assert c["dbq.req_drops"] == small["drops"] > 0
    assert c["chunks.req_escalated"] == c["chunks.retried"] > 0
    assert small["req_cap"] == 8 * 2 ** c["chunks.req_escalated"]
    assert c["jit.builds"] == c["chunks.req_escalated"] + 1
