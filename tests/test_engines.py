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
