"""Cross-engine conformance through the unified Executor API.

Every backend (ref / jax / dist) runs the same plan through the same
driver, so match counts must agree exactly — the correctness bar for
distributed subgraph matching is exact agreement, not approximation. Also
unit-tests the adaptive task-splitting driver itself: forced ENU overflow
must re-chunk the offending start batch (smaller frontiers, same
capacities) and never drop or duplicate a match.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.executor import (ceil_div, ChunkResult, ExecStats, Executor,
                                 ExecutorBackend, ExecutorConfig, drive,
                                 make_executor, plan_enu_count,
                                 split_id_batch)
from repro.core.pattern import get_pattern
from repro.core.plangen import generate_best_plan
from repro.core.ref_engine import enumerate_matches_brute
from repro.core.symmetry import symmetry_breaking_constraints
from repro.graph.generate import erdos_renyi, powerlaw

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# triangle, 4-cycle, 4-clique, 5-vertex house, 5-path, 5-cycle
PATTERNS = ["triangle", "square", "clique4", "house", "path5", "cycle5"]
GRAPHS = {
    "er": erdos_renyi(64, 256, seed=11),
    "pl": powerlaw(64, 4, seed=12),
}


_BRUTE_CACHE = {}


def brute_count(pname, g):
    key = (pname, id(g))
    if key not in _BRUTE_CACHE:
        p = get_pattern(pname)
        _BRUTE_CACHE[key] = len(enumerate_matches_brute(
            p, g, symmetry_breaking_constraints(p)))
    return _BRUTE_CACHE[key]


# --------------------------------------------------------------------------
# ref == jax on every pattern x graph (single device, in process)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("pname", PATTERNS)
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_ref_jax_conformance_unified_api(pname, gname):
    g = GRAPHS[gname]
    p = get_pattern(pname)
    plan = generate_best_plan(p, g.stats())
    ref = make_executor("ref").run(plan, g, batch=32)
    jx = make_executor("jax").run(plan, g, batch=32)
    want = brute_count(pname, g)
    assert ref.count == jx.count == want, (pname, gname)


# --------------------------------------------------------------------------
# jax-gpu (fused gather+intersect fetch path) == brute on every pattern x
# graph, with the fused Pallas kernel forced on in interpret mode — the
# only CI coverage the accelerator fetch path gets on the CPU container
# (ISSUE 5 acceptance bar). The fused kernel really fires here: triangle /
# square / clique4 / house all carry single-use DBQ operands
# (engine_jax.classify_fusable_dbqs); path5 / cycle5 pin the all-
# materialized degenerate case.
# --------------------------------------------------------------------------


@pytest.mark.parametrize("pname", PATTERNS)
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_jax_gpu_fused_conformance(pname, gname, monkeypatch):
    monkeypatch.setenv("REPRO_GATHER_INTERSECT_IMPL", "pallas-interpret")
    monkeypatch.delenv("REPRO_FUSED_FETCH", raising=False)
    g = GRAPHS[gname]
    p = get_pattern(pname)
    plan = generate_best_plan(p, g.stats())
    st = make_executor("jax-gpu").run(plan, g, batch=32)
    assert st.count == brute_count(pname, g), (pname, gname)
    assert st.extras["fused_fetch"] is True


def test_jax_gpu_fused_forced_overflow_match_set_exact():
    """Lazy DBQ id columns must survive re-chunking: the adaptive driver's
    split/escalate path with the fused kernel on neither drops nor
    duplicates matches."""
    p = get_pattern("clique4")
    g = GRAPHS["er"]
    plan = generate_best_plan(p, g.stats())
    n_enu = plan_enu_count(plan)
    ref = make_executor("ref").run(plan, g, batch=32, collect_matches=True)
    gpu = make_executor("jax-gpu", gather_intersect_impl="interpret").run(
        plan, g, batch=16, caps=[8] * n_enu, max_retries=12,
        collect_matches=True)
    got = {tuple(int(x) for x in row) for row in gpu.matches}
    want = {tuple(int(x) for x in row) for row in ref.matches}
    assert got == want
    assert len(gpu.matches) == len(got)
    assert gpu.chunks_split > 0


@pytest.mark.parametrize("engine", ["jax", "jax-gpu"])
def test_chunk_program_takes_adjacency_as_argument(engine):
    """The device adjacency is an argument of every compiled chunk program,
    never a constant inside it: jit copies a closed-over table into each
    program, which on a real-size graph is gigabytes of host memory."""
    import jax.numpy as jnp
    g = GRAPHS["pl"]
    plan = generate_best_plan(get_pattern("triangle"), g.stats())
    kw = {"gather_intersect_impl": "interpret"} if engine == "jax-gpu" else {}
    ex = make_executor(engine, **kw)
    assert ex.run(plan, g, batch=32).count == brute_count("triangle", g)
    be = ex.backend
    (B, _), runner = next(iter(be._runners.items()))
    lanes = be.dg.lane_rows if be.fused else None
    text = runner.lower(be.dg.rows, lanes, jnp.zeros(B, jnp.int32),
                        jnp.zeros(B, bool)).as_text()
    tables = {"x".join(map(str, be.dg.rows.shape))}
    if lanes is not None:
        tables.add("x".join(map(str, lanes.shape)))
    baked = [ln for ln in text.splitlines() if "constant" in ln
             and any(f"tensor<{t}xi32>" in ln for t in tables)]
    assert not baked, baked[:1]


# --------------------------------------------------------------------------
# oocache == brute on every pattern x graph, with the device cache bounded
# below 25% of the graph's rows (ISSUE 3 acceptance bar): the host-RAM
# store + bounded device cache must be a drop-in engine, not an
# approximation
# --------------------------------------------------------------------------


@pytest.mark.parametrize("pname", PATTERNS)
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_oocache_conformance_bounded_device_cache(pname, gname):
    g = GRAPHS[gname]
    p = get_pattern(pname)
    plan = generate_best_plan(p, g.stats())
    cap = max(1, int(g.n * 0.12))
    hot = max(1, int(g.n * 0.04))
    st = make_executor("oocache", cache_rows=cap, hot=hot).run(
        plan, g, batch=32)
    assert st.count == brute_count(pname, g), (pname, gname)
    # device residency — slab + both prefetch staging buffers + pinned
    # hot + sentinel, i.e. the whole footprint — under 25% of rows, and
    # the out-of-core path actually exercised (cold fetches happened)
    assert st.extras["device_resident_rows"] < 0.25 * (g.n + 1)
    assert st.extras["cache"]["cold_rows"] > 0


# --------------------------------------------------------------------------
# ref == jax == dist (8 forced host devices, one subprocess for all runs)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_three_engine_conformance_exact():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # forced host devices, off-chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
        import json
        from repro.core.executor import make_executor
        from repro.core.pattern import get_pattern
        from repro.core.plangen import generate_best_plan
        from repro.core.ref_engine import enumerate_matches_brute
        from repro.core.symmetry import symmetry_breaking_constraints
        from repro.graph.generate import powerlaw
        g = powerlaw(100, 4, seed=4)
        res = {}
        for pname in ("triangle", "square", "clique4", "house"):
            P = get_pattern(pname)
            plan = generate_best_plan(P, g.stats())
            brute = len(enumerate_matches_brute(
                P, g, symmetry_breaking_constraints(P)))
            ref = make_executor("ref").run(plan, g, batch=32).count
            jx = make_executor("jax").run(plan, g, batch=32).count
            ds = make_executor("dist", hot=8, rebalance=True).run(
                plan, g, batch=64).count
            res[pname] = dict(brute=brute, ref=ref, jax=jx, dist=ds)
        print(json.dumps(res))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(res) == {"triangle", "square", "clique4", "house"}
    for pname, r in res.items():
        assert r["ref"] == r["jax"] == r["dist"] == r["brute"], (pname, r)


# --------------------------------------------------------------------------
# Adaptive task splitting: forced ENU overflow re-chunks, never drops
# --------------------------------------------------------------------------


def test_forced_overflow_rechunks_and_stays_exact():
    p = get_pattern("house")
    g = GRAPHS["pl"]
    plan = generate_best_plan(p, g.stats())
    n_enu = plan_enu_count(plan)
    want = brute_count("house", g)
    # capacities far too small for a 16-start batch: the driver must split
    st = make_executor("jax").run(plan, g, batch=16, caps=[8] * n_enu,
                                  max_retries=12)
    assert st.count == want
    assert st.chunks_split > 0          # it re-chunked (did not just pad)
    assert st.chunks_run > st.chunks_split


def test_forced_overflow_match_set_exact_not_just_count():
    """Re-chunking must neither drop nor duplicate matches."""
    p = get_pattern("clique4")
    g = GRAPHS["er"]
    plan = generate_best_plan(p, g.stats())
    n_enu = plan_enu_count(plan)
    ref = make_executor("ref").run(plan, g, batch=32, collect_matches=True)
    jx = make_executor("jax").run(plan, g, batch=16, caps=[8] * n_enu,
                                  max_retries=12, collect_matches=True)
    got = {tuple(int(x) for x in row) for row in jx.matches}
    want = {tuple(int(x) for x in row) for row in ref.matches}
    assert got == want
    assert len(jx.matches) == len(got)  # no duplicates emitted


def test_overflow_disables_split_falls_back_to_caps():
    """adaptive_split=False reproduces the legacy capacity-doubling path."""
    p = get_pattern("house")
    g = GRAPHS["pl"]
    plan = generate_best_plan(p, g.stats())
    n_enu = plan_enu_count(plan)
    want = brute_count("house", g)
    st = make_executor("jax").run(plan, g, batch=16, caps=[8] * n_enu,
                                  max_retries=12, adaptive_split=False)
    assert st.count == want
    assert st.chunks_split == 0 and st.chunks_retried > 0


# --------------------------------------------------------------------------
# Driver unit tests on a deterministic fake backend (no jax involved)
# --------------------------------------------------------------------------


class FakeBackend(ExecutorBackend):
    """Each valid start yields exactly one match; a chunk 'overflows'
    whenever its demand (valid starts x fanout) exceeds caps[0]."""

    name = "fake"
    granularity = 1

    def __init__(self, n, fanout=1):
        self.n = n
        self.fanout = fanout
        self.seen = []                     # ids from successful chunks
        self.runs = 0

    def prepare(self, plan, source, config):
        self.sentinel = self.n

    def _n_starts(self):
        return self.n

    def initial_caps(self, config):
        return tuple(config.caps) if config.caps else (1,)

    def run_chunk(self, ids, valid, universe_chunk, caps):
        self.runs += 1
        nv = int(valid.sum())
        demand = nv * self.fanout
        if demand > caps[0]:
            return ChunkResult(count=0, overflow=demand - caps[0])
        self.seen.extend(int(v) for v in ids[valid])
        return ChunkResult(count=nv)


def test_driver_splits_to_fit_and_loses_nothing():
    be = FakeBackend(n=37)
    st = drive(be, None, None, ExecutorConfig(batch=16, caps=(2,)))
    assert st.count == 37
    assert sorted(be.seen) == list(range(37))      # every start exactly once
    assert st.chunks_split > 0
    assert st.chunks_retried == 0      # splitting alone fits caps=2


def test_driver_grows_caps_only_when_unsplittable():
    # fanout 4 with caps=1: even a single start overflows until caps reach 4
    be = FakeBackend(n=5, fanout=4)
    st = drive(be, None, None, ExecutorConfig(batch=4, caps=(1,)))
    assert st.count == 5
    assert sorted(be.seen) == list(range(5))
    assert st.chunks_retried > 0       # capacity-doubling was required
    assert st.chunks_split > 0         # after splitting down to singletons


def test_driver_raises_after_retry_budget():
    class AlwaysOverflow(FakeBackend):
        def run_chunk(self, ids, valid, universe_chunk, caps):
            return ChunkResult(count=0, overflow=1)

    be = AlwaysOverflow(n=4)
    with pytest.raises(RuntimeError, match="overflowed"):
        drive(be, None, None,
              ExecutorConfig(batch=4, caps=(1,), max_retries=3))


def test_split_id_batch_partitions_valid_ids():
    ids = np.arange(16, dtype=np.int32)
    valid = (ids % 3 != 0)
    halves = split_id_batch(ids, valid, granularity=1, sentinel=99)
    assert halves is not None and len(halves) == 2
    got = []
    for h_ids, h_valid in halves:
        assert h_ids.shape == (8,) and h_valid.shape == (8,)
        got.extend(int(v) for v in h_ids[h_valid])
    assert sorted(got) == sorted(int(v) for v in ids[valid])


def test_split_id_batch_odd_full_batch_drops_nothing():
    # B=5 all valid: halves get ceil(5/2)=3 and 2 ids — shape must fit 3
    ids = np.arange(5, dtype=np.int32)
    valid = np.ones(5, bool)
    halves = split_id_batch(ids, valid, granularity=1, sentinel=99)
    got = sorted(int(v) for h_ids, h_valid in halves
                 for v in h_ids[h_valid])
    assert got == list(range(5))


def test_driver_exact_with_odd_batch_under_overflow():
    be = FakeBackend(n=23)
    st = drive(be, None, None, ExecutorConfig(batch=7, caps=(2,)))
    assert st.count == 23
    assert sorted(be.seen) == list(range(23))


class MultipleBackend(FakeBackend):
    """FakeBackend advertising a mesh-style cap multiple; records every
    caps tuple the driver hands it."""

    cap_multiple = 8

    def __init__(self, n, fanout=1):
        super().__init__(n, fanout=fanout)
        self.caps_seen = []

    def run_chunk(self, ids, valid, universe_chunk, caps):
        self.caps_seen.append(tuple(caps))
        return super().run_chunk(ids, valid, universe_chunk, caps)


def test_driver_rounds_caps_to_backend_multiple():
    """The driver — not the backend — must keep every caps tuple it hands
    out divisible by cap_multiple (the rebalancer's ``cap % mesh size``
    contract): initial caps AND capacity-doubled ones. Regression for the
    `assert cap % n_shards == 0` crash on odd user/degree-derived caps."""
    be = MultipleBackend(n=20, fanout=4)
    st = drive(be, None, None, ExecutorConfig(batch=4, caps=(7,)))
    assert st.count == 20
    assert sorted(be.seen) == list(range(20))
    assert be.caps_seen and all(c % 8 == 0
                                for caps in be.caps_seen for c in caps)
    # odd initial caps rounded up (7 -> 8), not truncated down to 0
    assert min(c for caps in be.caps_seen for c in caps) >= 8


def test_driver_rounds_escalated_caps_to_multiple():
    class OddGrowth(MultipleBackend):
        def grow_caps(self, caps):
            return tuple(c * 2 + 1 for c in caps)   # always odd

    be = OddGrowth(n=3, fanout=40)
    st = drive(be, None, None,
               ExecutorConfig(batch=1, caps=(1,), max_retries=8))
    assert st.count == 3
    assert st.chunks_retried > 0
    assert all(c % 8 == 0 for caps in be.caps_seen for c in caps)


def test_split_id_batch_respects_granularity_and_floor():
    ids = np.arange(16, dtype=np.int32)
    valid = np.ones(16, bool)
    halves = split_id_batch(ids, valid, granularity=8, sentinel=99)
    assert all(h[0].shape == (8,) for h in halves)
    # a mesh-wide batch (B == granularity) cannot shrink further
    assert split_id_batch(ids[:8], valid[:8], granularity=8,
                          sentinel=99) is None
    assert split_id_batch(ids[:1], valid[:1], granularity=1,
                          sentinel=99) is None


def test_ceil_div_pins_half_computation():
    """The readable ceil-div form must reproduce the original
    quadruple-negation ``half`` expression bit for bit."""
    for B in range(2, 70):
        for granularity in (1, 2, 3, 4, 8, 16):
            legacy = -(-(-(-B // 2)) // granularity) * granularity
            assert ceil_div(ceil_div(B, 2), granularity) * granularity \
                == legacy, (B, granularity)
    assert ceil_div(0, 4) == 0
    assert ceil_div(1, 4) == 1
    assert ceil_div(8, 4) == 2
    assert ceil_div(9, 4) == 3


# --------------------------------------------------------------------------
# Streaming conformance: sbenu-jax == SBenuRefEngine == snapshot diff oracle
# over randomized insert/delete update streams
# --------------------------------------------------------------------------


SBENU_PATTERNS = ["dtoy", "q1'", "q2'", "q3'", "q5'"]


@pytest.mark.parametrize("pname", SBENU_PATTERNS)
def test_sbenu_jax_stream_conformance(pname):
    """ΔR_t^+ / ΔR_t^- must agree exactly across the vectorized engine,
    the interpreter, and the brute-force snapshot diff, on a randomized
    stream with both insertions and deletions."""
    from repro.core.estimate import GraphStats
    from repro.core.executor import SBenuJaxBackend
    from repro.core.sbenu import (generate_best_sbenu_plans, run_timestep,
                                  snapshot_diff_oracle)
    from repro.graph.dynamic import SnapshotStore
    from repro.graph.generate import edge_stream

    p = get_pattern(pname)
    g0, batches = edge_stream(n=24, m_init=110, steps=3, batch=24,
                              seed=17, delete_frac=0.4)
    store_jax = SnapshotStore(g0)
    store_ref = SnapshotStore(g0)
    plans = generate_best_sbenu_plans(p, GraphStats(24, 110,
                                                    delta_edges=24))
    backend = SBenuJaxBackend()          # reused: compiled once per stream
    for batch in batches:
        want_p, want_m = snapshot_diff_oracle(p, store_jax, batch)
        assert any(op == "-" for op, _, _ in batch)   # deletions exercised
        jp, jm, _ = run_timestep(p, plans, store_jax, batch,
                                 backend=backend, chunk=16)
        rp, rm, _ = run_timestep(p, plans, store_ref, batch, engine="ref")
        assert jp == rp == want_p
        assert jm == rm == want_m


def test_sbenu_jax_forced_overflow_stays_exact():
    """Tiny capacities force the adaptive driver to re-split delta chunks;
    the match sets must still be exact."""
    from repro.core.estimate import GraphStats
    from repro.core.executor import ExecutorConfig, SBenuJaxBackend, drive
    from repro.core.sbenu import (generate_best_sbenu_plans,
                                  snapshot_diff_oracle)
    from repro.graph.dynamic import SnapshotStore
    from repro.graph.generate import edge_stream

    p = get_pattern("q1'")
    g0, batches = edge_stream(n=40, m_init=250, steps=1, batch=40, seed=5)
    store = SnapshotStore(g0)
    plans = generate_best_sbenu_plans(p, GraphStats(40, 250,
                                                    delta_edges=40))
    want_p, want_m = snapshot_diff_oracle(p, store, batches[0])
    store.begin_step(batches[0])
    st = drive(SBenuJaxBackend(), plans, store,
               ExecutorConfig(batch=32, caps=[4, 4, 4], max_retries=12,
                              collect_matches=True))
    store.end_step()
    assert st.extras["delta_plus"] == want_p
    assert st.extras["delta_minus"] == want_m
    assert st.chunks_split > 0


# --------------------------------------------------------------------------
# Distributed streaming conformance: sbenu-dist == interpreter == oracle.
# In-process runs use the default single device (S=1 makes the typed-DBQ
# all_to_alls local exchanges — fast tier); the 8-way matrix including the
# rebalancer + forced-overflow re-split runs in a subprocess (slow tier).
# --------------------------------------------------------------------------


def test_sbenu_dist_stream_conformance_single_device():
    from repro.core.estimate import GraphStats
    from repro.core.executor import SBenuDistBackend
    from repro.core.sbenu import (generate_best_sbenu_plans, run_timestep,
                                  snapshot_diff_oracle)
    from repro.graph.dynamic import SnapshotStore, stream_width_floors
    from repro.graph.generate import edge_stream

    for pname in ("dtoy", "q1'"):
        p = get_pattern(pname)
        g0, batches = edge_stream(n=24, m_init=110, steps=2, batch=24,
                                  seed=17, delete_frac=0.4)
        store = SnapshotStore(g0)
        plans = generate_best_sbenu_plans(p, GraphStats(24, 110,
                                                        delta_edges=24))
        d, dd = stream_width_floors(g0, batches)
        # widths pinned over the stream: the sharded blocks stay resident
        backend = SBenuDistBackend(hot=4, d_min=d, delta_d_min=dd)
        for batch in batches:
            want_p, want_m = snapshot_diff_oracle(p, store, batch)
            assert any(op == "-" for op, _, _ in batch)
            dp, dm, _ = run_timestep(p, plans, store, batch,
                                     backend=backend, chunk=16)
            assert dp == want_p and dm == want_m, pname
        # the sharded snapshot stayed resident (one initial build only)
        assert backend.dstore.rebuilds == 1


@pytest.mark.slow
def test_sbenu_dist_eight_way_stream_matrix():
    """The full randomized-stream matrix on an 8-way host mesh, with hot
    rows + the frontier rebalancer on, plus the forced-overflow re-split
    case with odd caps — the regression for the driver handing the
    rebalancer capacities not divisible by the mesh size
    (`assert cap % n_shards == 0`, core/engine_dist.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # forced host devices, off-chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
        import json
        from repro.core.estimate import GraphStats
        from repro.core.pattern import get_pattern
        from repro.core.executor import (ExecutorConfig, SBenuDistBackend,
                                         drive)
        from repro.core.sbenu import (generate_best_sbenu_plans,
                                      run_timestep, snapshot_diff_oracle)
        from repro.graph.dynamic import SnapshotStore
        from repro.graph.generate import edge_stream

        res = {}
        for pname in ("dtoy", "q1'", "q2'", "q3'", "q5'"):
            p = get_pattern(pname)
            g0, batches = edge_stream(n=24, m_init=110, steps=2, batch=24,
                                      seed=17, delete_frac=0.4)
            store = SnapshotStore(g0)
            store_ref = SnapshotStore(g0)
            store_jax = SnapshotStore(g0)
            plans = generate_best_sbenu_plans(
                p, GraphStats(24, 110, delta_edges=24))
            backend = SBenuDistBackend(hot=4, rebalance=True)
            ok = True
            for batch in batches:
                want_p, want_m = snapshot_diff_oracle(p, store, batch)
                dp, dm, _ = run_timestep(p, plans, store, batch,
                                         backend=backend, chunk=16)
                rp, rm, _ = run_timestep(p, plans, store_ref, batch,
                                         engine="ref")
                jp, jm, _ = run_timestep(p, plans, store_jax, batch,
                                         engine="sbenu-jax", chunk=16)
                ok = ok and dp == rp == jp == want_p
                ok = ok and dm == rm == jm == want_m
            res[pname] = ok

        # forced overflow with ODD caps on the 8-way mesh: the driver must
        # round to the mesh multiple (previously: rebalancer assert crash)
        p = get_pattern("q1'")
        g0, batches = edge_stream(n=40, m_init=250, steps=1, batch=40,
                                  seed=5)
        store = SnapshotStore(g0)
        plans = generate_best_sbenu_plans(
            p, GraphStats(40, 250, delta_edges=40))
        want_p, want_m = snapshot_diff_oracle(p, store, batches[0])
        store.begin_step(batches[0])
        st = drive(SBenuDistBackend(rebalance=True), plans, store,
                   ExecutorConfig(batch=32, caps=[7, 7, 7],
                                  max_retries=12, collect_matches=True))
        store.end_step()
        res["odd_caps_exact"] = (st.extras["delta_plus"] == want_p
                                 and st.extras["delta_minus"] == want_m)
        # tiny even caps actually exercise the mesh-wide re-split path
        store2 = SnapshotStore(g0)
        want_p2, want_m2 = snapshot_diff_oracle(p, store2, batches[0])
        store2.begin_step(batches[0])
        st2 = drive(SBenuDistBackend(), plans, store2,
                    ExecutorConfig(batch=32, caps=[2, 2, 2],
                                   max_retries=12, collect_matches=True))
        store2.end_step()
        res["overflow_exact"] = (st2.extras["delta_plus"] == want_p2
                                 and st2.extras["delta_minus"] == want_m2)
        res["overflow_split"] = int(st2.chunks_split)
        print(json.dumps(res))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for pname in ("dtoy", "q1'", "q2'", "q3'", "q5'"):
        assert res[pname], pname
    assert res["odd_caps_exact"]
    assert res["overflow_exact"]
    assert res["overflow_split"] > 0


# --------------------------------------------------------------------------
# The Pallas INT path on CPU: REPRO_INTERSECT_IMPL=pallas-interpret routes
# every auto intersect through the Pallas kernel in interpret mode — both
# the static frontier engine and the streaming delta engine must stay
# exact (this is the only CI coverage the TPU kernel dispatch path gets)
# --------------------------------------------------------------------------


def test_intersect_pallas_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_INTERSECT_IMPL", "pallas-interpret")
    from repro.core.engine_sbenu_jax import _resolve_intersect_impl
    from repro.kernels.dispatch import resolve_impl
    assert _resolve_intersect_impl("auto") == "interpret"
    assert _resolve_intersect_impl("binary") == "binary"   # explicit wins
    # the streaming resolver is a veneer over the shared dispatch registry
    assert resolve_impl("intersect") == "interpret"
    monkeypatch.delenv("REPRO_INTERSECT_IMPL")
    assert _resolve_intersect_impl("auto") == "binary"     # CPU default
    # the literal env value "auto" is a reset, not an override: the
    # streaming engine must keep its binary-probe CPU default
    monkeypatch.setenv("REPRO_INTERSECT_IMPL", "auto")
    assert _resolve_intersect_impl("auto") == "binary"
    monkeypatch.setenv("REPRO_INTERSECT_IMPL", "pallas-interpret")

    # static path (engine_jax -> kernels.ops dispatch)
    g = GRAPHS["er"]
    p = get_pattern("triangle")
    plan = generate_best_plan(p, g.stats())
    st = make_executor("jax").run(plan, g, batch=32)
    assert st.count == brute_count("triangle", g)

    # streaming path (mixed-width intersects: delta rows x adjacency rows)
    from repro.core.estimate import GraphStats
    from repro.core.executor import SBenuJaxBackend
    from repro.core.sbenu import (generate_best_sbenu_plans, run_timestep,
                                  snapshot_diff_oracle)
    from repro.graph.dynamic import SnapshotStore
    from repro.graph.generate import edge_stream
    sp = get_pattern("q1'")
    g0, batches = edge_stream(n=24, m_init=110, steps=1, batch=20, seed=3)
    store = SnapshotStore(g0)
    plans = generate_best_sbenu_plans(sp, GraphStats(24, 110,
                                                     delta_edges=20))
    want_p, want_m = snapshot_diff_oracle(sp, store, batches[0])
    dp, dm, _ = run_timestep(sp, plans, store, batches[0],
                             backend=SBenuJaxBackend(), chunk=16)
    assert dp == want_p and dm == want_m
