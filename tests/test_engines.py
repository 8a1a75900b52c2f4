"""Engine equivalence: reference interpreter == brute force == vectorized
JAX frontier engine (incl. VCBC closed-form counting and V(G) wedge plans)."""

import numpy as np
import pytest

from repro.core.engine_jax import enumerate_graph
from repro.core.pattern import get_pattern
from repro.core.plangen import generate_best_plan, generate_optimized_plan
from repro.core.ref_engine import (GraphDB, RefEngine,
                                   count_isomorphic_subgraphs,
                                   enumerate_matches_brute)
from repro.core.symmetry import symmetry_breaking_constraints
from repro.graph.generate import erdos_renyi, powerlaw, toy_graph_fig1

GRAPHS = {
    "toy": toy_graph_fig1(),
    "er": erdos_renyi(50, 200, seed=1),
    "pl": powerlaw(50, 4, seed=2),
}
PATTERNS = ["triangle", "square", "chordal-square", "clique4", "house",
            "q6", "fan5"]


@pytest.mark.parametrize("pname", PATTERNS)
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_ref_vs_brute_vs_jax(pname, gname):
    p = get_pattern(pname)
    g = GRAPHS[gname]
    plan = generate_best_plan(p, g.stats())
    ref = RefEngine(plan, p, g)
    ref.run()
    brute = len(enumerate_matches_brute(
        p, g, symmetry_breaking_constraints(p)))
    jres = enumerate_graph(plan, g, batch=32)
    assert ref.counters.matches == brute == jres["count"]


@pytest.mark.parametrize("pname", ["triangle", "chordal-square", "house"])
def test_jax_vcbc_counts(pname):
    p = get_pattern(pname)
    g = GRAPHS["pl"]
    brute = len(enumerate_matches_brute(
        p, g, symmetry_breaking_constraints(p)))
    plan = generate_best_plan(p, g.stats(), vcbc=True)
    try:
        res = enumerate_graph(plan, g, batch=32)
    except NotImplementedError:
        pytest.skip(">2 non-core vertices")
    assert res["count"] == brute


def test_match_sets_equal_not_just_counts():
    p = get_pattern("chordal-square")
    g = GRAPHS["er"]
    plan = generate_best_plan(p, g.stats())
    ref = RefEngine(plan, p, g, collect="matches")
    ref.run()
    res = enumerate_graph(plan, g, batch=16, collect_matches=True)
    got = {tuple(int(x) for x in row) for row in res["matches"]}
    assert got == set(ref.matches)


def test_subgraph_count_via_automorphisms():
    p = get_pattern("triangle")
    g = GRAPHS["er"]
    cnt = count_isomorphic_subgraphs(p, g)
    plan = generate_best_plan(p, g.stats())
    res = enumerate_graph(plan, g, batch=32)
    assert res["count"] == cnt         # symmetry breaking = 1 match/subgraph


@pytest.mark.parametrize("block", [1, 7, 64, 1 << 14])
def test_device_graph_blocked_build_matches_padded_table(block, monkeypatch):
    """The table built on device block by block is the host padded table
    plus the all-sentinel row, whatever the block size."""
    from repro.core import engine_jax
    from repro.core.engine_jax import DeviceGraph
    monkeypatch.setattr(engine_jax, "HOST_BLOCK_ROWS", block)
    g = GRAPHS["pl"]
    rows, _ = g.padded_adjacency(lane=128)
    want = np.concatenate([rows, np.full((1, rows.shape[1]), g.n,
                                         np.int32)])
    dg = DeviceGraph.from_graph(g)
    np.testing.assert_array_equal(np.asarray(dg.rows), want)
    with pytest.raises(ValueError, match="truncated"):
        DeviceGraph.from_graph(g, d_max=2, lane=1)


def test_overflow_retry_is_exact():
    """Tiny capacities force overflow; the driver must still be exact."""
    p = get_pattern("house")
    g = GRAPHS["pl"]
    plan = generate_best_plan(p, g.stats())
    brute = len(enumerate_matches_brute(
        p, g, symmetry_breaking_constraints(p)))
    n_enu = sum(1 for i in plan.instrs if i.op == "ENU")
    res = enumerate_graph(plan, g, batch=8, caps=[16] * n_enu,
                          max_retries=12)
    assert res["count"] == brute
    assert res["chunks_retried"] > 0   # the tiny caps actually overflowed


def test_db_cache_hit_rate_locality():
    """Paper Fig. 10: bigger cache => fewer remote queries."""
    p = get_pattern("chordal-square")
    g = GRAPHS["pl"]
    plan = generate_best_plan(p, g.stats())
    remote = []
    for cap in (0, 8, g.n):
        db = GraphDB(g, cache_capacity=cap)
        eng = RefEngine(plan, p, g, db=db)
        eng.run()
        remote.append(db.remote_queries)
    assert remote[0] >= remote[1] >= remote[2]
    assert remote[2] <= g.n            # full cache: each row fetched once


def test_task_splitting_bounds_work():
    """Paper Fig. 11: theta splitting caps per-task work spread."""
    p = get_pattern("triangle")
    g = powerlaw(80, 6, seed=3)
    plan = generate_best_plan(p, g.stats())
    eng_a = RefEngine(plan, p, g)
    eng_a.run()
    eng_b = RefEngine(plan, p, g)
    eng_b.run(theta=8)
    assert eng_a.counters.matches == eng_b.counters.matches
    assert max(eng_b.counters.per_task_work) <= \
        max(eng_a.counters.per_task_work)
    assert len(eng_b.counters.per_task_work) > \
        len(eng_a.counters.per_task_work)


# (B, D, hole share, invalid-parent share, cap from (total, B * D))
EXPAND_CASES = {
    "cap-below": (16, 8, 0.5, 0.0, lambda t, n: t // 2),
    "cap-at": (16, 8, 0.5, 0.0, lambda t, n: t),
    "cap-above": (16, 8, 0.5, 0.0, lambda t, n: t + 5),
    "cap-above-BxD": (16, 8, 0.5, 0.0, lambda t, n: n + 7),
    "all-holes": (16, 8, 1.0, 0.0, lambda t, n: 10),
    "no-holes": (16, 8, 0.0, 0.0, lambda t, n: t - 3),
    "invalid-parents": (32, 16, 0.05, 0.3, lambda t, n: t - 1),
    "one-row": (1, 16, 0.5, 0.0, lambda t, n: t),
    "one-column": (40, 1, 0.5, 0.0, lambda t, n: t // 2),
    "rows-over-128": (300, 5, 0.8, 0.1, lambda t, n: t),
    "rows-over-128x128": (16500, 2, 0.9, 0.0, lambda t, n: t - 10),
}


@pytest.mark.parametrize("case", sorted(EXPAND_CASES))
def test_expand_matches_flat_order_compaction(case):
    """ENU compaction keeps the valid candidates in flat row-major order,
    carries the live columns and extra columns of each child's parent
    and candidate, and counts what does not fit as overflow: the same
    arrays as a plain numpy compaction, and as ``compaction="sort"``."""
    from repro.core.engine_jax import _expand
    B, D, holes, invalid, cap_of = EXPAND_CASES[case]
    S = 1000
    rng = np.random.default_rng(B * 1000 + D)
    cand = np.where(rng.random((B, D)) < holes, S,
                    rng.integers(0, S, (B, D))).astype(np.int32)
    valid = rng.random(B) >= invalid
    col = rng.integers(0, S, B).astype(np.int32)
    sel = rng.integers(0, 2, (B, D)).astype(np.int32)
    fvalid = ((cand != S) & valid[:, None]).reshape(-1)
    idx = np.nonzero(fvalid)[0]
    cap = cap_of(len(idx), B * D)
    kept = idx[:cap]
    k = len(kept)

    def pad(x, fill):
        return np.concatenate([x, np.full(cap - k, fill, x.dtype)])

    want = {("A", 0): pad(col[kept // D], col[0]),
            ("A", 1): pad(cand.reshape(-1)[kept], S),
            ("s", 1): pad(sel.reshape(-1)[kept], 0)}
    want_valid = np.arange(cap) < k
    args = ({("A", 0): col, ("f", 0): col}, valid, cand, ("A", 1), cap,
            frozenset({("A", 0)}), S)
    env, new_valid, overflow = _expand(*args, extra_cols={("s", 1): sel})
    assert set(env) == set(want)
    for v, arr in want.items():
        np.testing.assert_array_equal(np.asarray(env[v]), arr, err_msg=v)
    np.testing.assert_array_equal(np.asarray(new_valid), want_valid)
    assert int(overflow) == max(len(idx) - cap, 0)
    if cap <= B * D:         # the sort path takes at most B * D children
        env_s, valid_s, overflow_s = _expand(
            *args, compaction="sort", extra_cols={("s", 1): sel})
        np.testing.assert_array_equal(np.asarray(valid_s), want_valid)
        for v, arr in env.items():
            np.testing.assert_array_equal(np.asarray(env_s[v])[:k],
                                          np.asarray(arr)[:k], err_msg=v)
        assert int(overflow_s) == int(overflow)
