"""The engine's own spans, counters and device scopes (``repro.obs``).

Spans nest and carry their request key, the ring and the per-key
counters stay bounded, spans reach the profiler's host plane, the chunk
programs and ``derive`` name their device work by layer, and recording
changes no answer.
"""

import collections
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.estimate import GraphStats
from repro.core.executor import (ExecutorConfig, JaxBackend,
                                 SBenuJaxBackend, drive, make_executor)
from repro.core.pattern import get_pattern
from repro.core.plangen import generate_best_plan
from repro.core.sbenu import (generate_best_sbenu_plans, run_timestep,
                              snapshot_diff_oracle)
from repro.graph.dynamic import DeviceSnapshotStore, SnapshotStore
from repro.graph.generate import edge_stream, powerlaw


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()
    yield
    obs.reset()


def _by_name(recs):
    out = collections.defaultdict(list)
    for r in recs:
        out[r.name].append(r)
    return out


# --------------------------------------------------------------- recorder


def test_spans_nest_with_parents_and_inherit_keys():
    with obs.span("timestep", key=3):
        with obs.span("prepare"):
            with obs.span("snapshot.place"):
                pass
        with obs.span("drive", key="other"):
            pass
    with obs.span("free"):
        pass
    r = {x.name: x for x in obs.records()}
    assert r["timestep"].parent_id is None
    assert r["prepare"].parent_id == r["timestep"].span_id
    assert r["snapshot.place"].parent_id == r["prepare"].span_id
    assert r["snapshot.place"].key == r["prepare"].key == 3
    assert r["drive"].key == "other"          # an explicit key wins
    assert r["free"].key is None and r["free"].parent_id is None
    # children end first, and each lies inside its parent
    names = [x.name for x in obs.records()]
    assert names.index("snapshot.place") < names.index("prepare") \
        < names.index("timestep")
    for child, parent in (("snapshot.place", "prepare"),
                          ("prepare", "timestep")):
        assert r[parent].t0_ns <= r[child].t0_ns <= r[child].t1_ns \
            <= r[parent].t1_ns
    assert obs.current_key() is None


def test_span_closes_when_its_body_raises():
    with pytest.raises(ValueError):
        with obs.span("timestep", key=1):
            raise ValueError("x")
    assert obs.current_key() is None
    assert [x.name for x in obs.records()] == ["timestep"]


def test_ring_keeps_the_newest_spans(monkeypatch):
    monkeypatch.setattr(obs, "_records", collections.deque(maxlen=4))
    for i in range(10):
        with obs.span("s", key=i):
            pass
    assert [x.key for x in obs.records()] == [6, 7, 8, 9]


def test_counters_per_key_and_in_total(monkeypatch):
    monkeypatch.setattr(obs, "KEYS", 3)
    obs.count("outside", 5)                   # no key: total only
    for t in range(1, 6):
        with obs.span("timestep", key=t):
            with obs.span("snapshot.place"):
                obs.count("snapshot.h2d_bytes", 100 * t)
            obs.count("delta.plus", t)
    assert obs.counters()["snapshot.h2d_bytes"] == 1500
    assert obs.counters()["outside"] == 5
    assert obs.counters(key=5) == {"snapshot.h2d_bytes": 500,
                                   "delta.plus": 5}
    assert obs.counters(key=1) == {}          # the oldest keys are dropped
    assert len(obs._by_key) == 3


def test_self_times_subtract_children():
    R = obs.Record
    recs = [R("a", 2, 1, None, 10, 40), R("b", 3, 1, None, 50, 60),
            R("top", 1, None, None, 0, 100)]
    st = obs.self_times(recs)
    assert st["top"] == pytest.approx(60e-9)
    assert st["a"] == pytest.approx(30e-9) and st["b"] == pytest.approx(10e-9)


def test_build_on_first_call_spans_the_first_call_only():
    fn = obs.build_on_first_call(jax.jit(lambda x: x + 1))
    for _ in range(3):
        fn(jnp.zeros(4))
    assert [x.name for x in obs.records()] == ["jit.build"]
    assert obs.counters()["jit.builds"] == 1
    assert "add" in fn.lower(jnp.zeros(4)).as_text()   # still a jit


def test_spans_reach_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("timestep", key=1):
            with obs.span("chunk.wait"):
                f(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    host = {e.name for p in ProfileData.from_file(str(pb)).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events}
    assert {"repro.timestep", "repro.chunk.wait"} <= host


# --------------------------------------------------------------- scopes


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes(compiled_text):
    return {part for name in _op_names(compiled_text)
            for part in name.split("/")}


def test_triangle_chunk_program_names_enu_int_dbq():
    g = powerlaw(200, 3, seed=1)
    plan = generate_best_plan(get_pattern("triangle"), g.stats())
    be = JaxBackend()
    drive(be, plan, g, ExecutorConfig(batch=32))
    (B, caps), runner = next(iter(be._runners.items()))
    text = runner.lower(be.dg.rows, None, jnp.zeros(B, jnp.int32),
                        jnp.zeros(B, bool)).compile().as_text()
    assert {"enu", "int", "dbq"} <= _scopes(text)


def _small_stream(steps=3):
    g0, batches = edge_stream(n=60, m_init=240, steps=steps, batch=30,
                              seed=4)
    pattern = get_pattern("q1'")
    plans = generate_best_sbenu_plans(pattern, GraphStats(60, 240,
                                                          delta_edges=30))
    return g0, batches, pattern, plans


def test_sbenu_chunk_program_and_derive_name_their_work():
    g0, batches, pattern, plans = _small_stream(1)
    store = SnapshotStore(g0)
    be = SBenuJaxBackend(collect="matches")
    run_timestep(pattern, plans, store, batches[0], engine="sbenu-jax",
                 backend=be, chunk=16)
    key, runner = next(iter(be._runners.items()))
    B = key[1]
    text = runner.lower(be.snap, jnp.zeros(B, jnp.int32),
                        jnp.zeros(B, bool)).compile().as_text()
    assert {"enu", "int", "dbq"} <= _scopes(text)
    mirror = store._mirrors[0]
    prev = mirror._prev["out"]
    d = mirror._derive.lower(
        prev, jnp.full(4, store.n, jnp.int32),
        jnp.full((4, 8), store.n, jnp.int32),
        jnp.zeros((4, 8), jnp.int32)).compile().as_text()
    assert "derive" in _scopes(d)


# --------------------------------------------------------------- engine


def test_census_counts_equal_the_oracle_with_spans_recorded():
    g = powerlaw(300, 3, seed=2)
    plan = generate_best_plan(get_pattern("triangle"), g.stats())
    want = make_executor("ref").run(plan, g, batch=64).count
    obs.reset()
    got = make_executor("jax").run(plan, g, batch=32, caps=[64, 64])
    assert got.count == want
    by = _by_name(obs.records())
    drives = by["drive"]
    assert len(drives) == 1 and drives[0].key[0] == "drive"
    assert len(by["chunk"]) == got.chunks_run == obs.counters()["chunks.run"]
    assert obs.counters().get("chunks.split", 0) == got.chunks_split > 0
    for name in ("chunk.dispatch", "chunk.wait", "prepare", "jit.build"):
        assert by[name], name
    # every chunk sub-span sits inside a chunk span, under the drive key
    chunk_ids = {r.span_id for r in by["chunk"]}
    assert all(r.parent_id in chunk_ids for r in by["chunk.wait"])
    assert {r.key for r in obs.records()} == {drives[0].key}


def test_stream_deltas_equal_the_oracle_with_spans_recorded():
    g0, batches, pattern, plans = _small_stream(3)
    store = SnapshotStore(g0)
    be = SBenuJaxBackend(collect="matches")
    for t, batch in enumerate(batches, 1):
        want = snapshot_diff_oracle(pattern, store, batch)
        plus, minus, ctr = run_timestep(pattern, plans, store, batch,
                                        engine="sbenu-jax", backend=be,
                                        chunk=16)
        assert (plus, minus) == want
        c = obs.counters(key=t)
        assert c.get("delta.plus", 0) == len(plus)
        assert c.get("delta.minus", 0) == len(minus)
    by = _by_name(obs.records())
    assert [r.key for r in by["timestep"]] == [1, 2, 3]
    for name in ("store.begin_step", "store.end_step", "drive", "prepare",
                 "snapshot.fit", "snapshot.delta_buffers", "snapshot.place",
                 "snapshot.derive", "chunk", "chunk.wait"):
        assert {r.key for r in by[name]} <= {1, 2, 3}, name
        assert by[name], name
    assert len(by["snapshot.rebuild"]) == 1     # the first step only
    assert obs.counters()["snapshot.rebuilds"] == 1
    assert store._mirrors[0].rebuilds == 1
    assert len(by["snapshot.delta_buffers"]) == len(batches)


def test_h2d_bytes_count_the_placed_buffers(monkeypatch):
    g0, batches, pattern, plans = _small_stream(3)
    placed = collections.Counter()
    for hook in ("_place", "_place_touched"):   # every upload goes here
        orig = getattr(DeviceSnapshotStore, hook)

        def place(self, arr, orig=orig):
            placed[obs.current_key()] += np.asarray(arr).nbytes
            return orig(self, arr)

        monkeypatch.setattr(DeviceSnapshotStore, hook, place)
    store = SnapshotStore(g0)
    be = SBenuJaxBackend(collect="matches")
    for t, batch in enumerate(batches, 1):
        run_timestep(pattern, plans, store, batch, engine="sbenu-jax",
                     backend=be, chunk=16)
        assert obs.counters(key=t)["snapshot.h2d_bytes"] == placed[t]
        # after the first step only each direction's touched rows go up:
        # ids, values and signs, K = the touched count padded to a power
        # of two
        if t > 1:
            assert placed[t] == sum(
                4 * (1 << max(len({u[i] for u in batch}) - 1, 0)
                     .bit_length()) * (1 + 2 * dv.shape[1])
                for i, dv in ((1, be.snap.delta_out), (2, be.snap.delta_in)))
    assert placed[1] > placed[2]                # step 1 built prev too
