"""Vectorized S-BENU: the six-block device layout, the device-resident
dual-snapshot store, the JIT delta-frontier engine, and the padded-row
truncation guard."""

import warnings

import numpy as np
import pytest

from repro import obs
from repro.core.estimate import GraphStats
from repro.core.pattern import get_pattern
from repro.core.sbenu import generate_best_sbenu_plans, snapshot_diff_oracle
from repro.graph.dynamic import (DeviceSnapshotStore, SnapshotStore,
                                 stream_width_floors)
from repro.graph.generate import edge_stream
from repro.graph.storage import DiGraph, Graph

# --------------------------------------------------------------------------
# storage: padded-row truncation is loud, never silent
# --------------------------------------------------------------------------


def test_padded_adjacency_truncation_raises():
    g = Graph.from_edges(5, [(0, 1), (0, 2), (0, 3), (0, 4)])
    with pytest.raises(ValueError, match="truncated"):
        g.padded_adjacency(d_max=2, lane=1)


def test_padded_adjacency_truncation_clamp_warns():
    g = Graph.from_edges(5, [(0, 1), (0, 2), (0, 3), (0, 4)])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rows, deg = g.padded_adjacency(d_max=2, lane=1,
                                       on_overflow="clamp")
    assert len(w) == 1 and issubclass(w[0].category, RuntimeWarning)
    hub = int(np.argmax(deg))
    assert (rows[hub] != g.n).sum() == 2    # clamped to the padded width

    # a d_max under the max degree whose lane-rounded width still fits,
    # exact widths, and default widths all stay silent
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        g.padded_adjacency(d_max=2)          # lane=8 rounds up to 8 >= 4
        g.padded_adjacency(d_max=4, lane=1)
        g.padded_adjacency()
    assert not w


def test_digraph_padded_adjacency_directions():
    g = DiGraph.from_edges(4, [(0, 1), (0, 2), (3, 0)])
    out = g.padded_adjacency("out")
    inn = g.padded_adjacency("in")
    assert {int(x) for x in out[0] if x != 4} == {1, 2}
    assert {int(x) for x in inn[0] if x != 4} == {3}


def _small_digraph():
    return DiGraph.from_edges(4, [(0, 1), (0, 2), (1, 2), (3, 1)])


def _dup_and_loop():
    return DiGraph.from_edges(5, [(0, 1), (0, 1), (2, 2), (3, 4), (4, 3)])


def _add_existing():
    g = _small_digraph()
    g.add_edge(0, 1)
    g.add_edge(1, 2)
    return g


def _remove_missing():
    g = _small_digraph()
    g.remove_edge(2, 0)                 # (0, 2) is there, (2, 0) is not
    g.remove_edge(0, 2)
    g.remove_edge(0, 2)
    return g


def _mutated_copy():
    g = _small_digraph()
    c = g.copy()
    c.add_edge(3, 0)
    c.remove_edge(0, 1)
    c.remove_edge(1, 2)
    assert g.m == 4 == sum(len(s) for s in g.out)   # original untouched
    assert g.has_edge(0, 1) and not g.has_edge(3, 0)
    return c


@pytest.mark.parametrize("build", [_dup_and_loop, _add_existing,
                                   _remove_missing, _mutated_copy])
def test_digraph_edge_count_is_kept(build):
    g = build()
    assert g.m == sum(len(s) for s in g.out) == sum(len(s) for s in g.inn)


# --------------------------------------------------------------------------
# storage: host-built six-block snapshot vs the dict-based get_adj
# --------------------------------------------------------------------------


def _row_set(rows, v, sentinel):
    return {int(x) for x in rows[v] if x != sentinel}


def test_device_snapshot_matches_get_adj():
    g0, batches = edge_stream(n=30, m_init=130, steps=1, batch=24, seed=7)
    store = SnapshotStore(g0)
    store.begin_step(batches[0])
    snap = store.device_snapshot()
    n = store.n
    blocks = {"out": (snap.prev_out, snap.cur_out, snap.delta_out,
                      snap.delta_out_sign),
              "in": (snap.prev_in, snap.cur_in, snap.delta_in,
                     snap.delta_in_sign)}
    for v in range(n):
        for di, (prev, cur, dv, ds) in blocks.items():
            assert _row_set(prev, v, n) == \
                set(store.get_adj(v, "either", di, "-"))
            assert _row_set(cur, v, n) == \
                set(store.get_adj(v, "either", di, "+"))
            plus = {int(x) for x, s in zip(dv[v], ds[v]) if s == 1}
            minus = {int(x) for x, s in zip(dv[v], ds[v]) if s == -1}
            assert plus == set(store.get_adj(v, "delta", di, "+"))
            assert minus == set(store.get_adj(v, "delta", di, "-"))
            assert _row_set(prev, v, n) - minus == \
                set(store.get_adj(v, "unaltered", di, "+"))
    # sentinel row is all holes / zero signs
    assert (snap.prev_out[n] == n).all()
    assert (snap.delta_in_sign[n] == 0).all()
    store.end_step()


def test_device_snapshot_store_tracks_host_across_steps():
    """The device-resident mirror must agree with a fresh host build on
    every step (its prev advances by on-device sort-compaction)."""
    g0, batches = edge_stream(n=30, m_init=140, steps=4, batch=25, seed=9)
    store = SnapshotStore(g0)
    ds = DeviceSnapshotStore.for_store(store)
    assert DeviceSnapshotStore.for_store(store) is ds   # mirror reuse
    for batch in batches:
        store.begin_step(batch)
        got = ds.step_snapshot()
        want = store.device_snapshot()
        n = store.n
        for v in range(n):
            for g_rows, w_rows in ((got.prev_out, want.prev_out),
                                   (got.cur_out, want.cur_out),
                                   (got.prev_in, want.prev_in),
                                   (got.cur_in, want.cur_in)):
                assert _row_set(np.asarray(g_rows), v, n) == \
                    _row_set(np.asarray(w_rows), v, n), v
        store.end_step()
    assert ds.rebuilds >= 1              # initial build only (no overflow)


def test_device_snapshot_store_invalidates_when_bypassed():
    """Steps run without the mirror (interpreter-only) must not leave it
    stale: the next use rebuilds from the host store."""
    g0, batches = edge_stream(n=20, m_init=80, steps=3, batch=15, seed=4)
    store = SnapshotStore(g0)
    ds = DeviceSnapshotStore.for_store(store)
    store.begin_step(batches[0])
    ds.step_snapshot()
    store.end_step()
    store.begin_step(batches[1])         # mirror not consulted this step
    store.end_step()
    store.begin_step(batches[2])
    got = ds.step_snapshot()
    want = store.device_snapshot()
    n = store.n
    for v in range(n):
        assert _row_set(np.asarray(got.cur_out), v, n) == \
            _row_set(np.asarray(want.cur_out), v, n)
    store.end_step()
    assert ds.rebuilds >= 2


_BLOCKS = ("prev_out", "prev_in", "cur_out", "cur_in", "delta_out",
           "delta_out_sign", "delta_in", "delta_in_sign")


@pytest.mark.parametrize("seed", [3, 11, 23])
def test_device_snapshot_store_blocks_equal_host_build_bitwise(seed):
    """The mirror sends only touched rows up and builds the dense delta
    blocks on device; every block must still equal the host build bit for
    bit, through a step with no deltas at all and one with a vertex
    holding a full delta row (Dd updates)."""
    g0, batches = edge_stream(n=40, m_init=150, steps=3, batch=20,
                              seed=seed)
    store = SnapshotStore(g0)
    ds = DeviceSnapshotStore(store, d_min=40)       # no width rebuilds

    def check(batch):
        store.begin_step(batch)
        got = ds.step_snapshot()
        want = store.device_snapshot(d_min=40)
        for name in _BLOCKS:
            g, w = np.asarray(getattr(got, name)), getattr(want, name)
            assert g.dtype == w.dtype and np.array_equal(g, w), name
        store.end_step()

    for batch in batches:
        check(batch)
    check([])                                       # no in- or out-deltas
    v, dd = 0, 8
    outs = sorted(store.prev.out[v])[:dd // 2]
    news = [w for w in range(1, store.n) if w not in store.prev.out[v]]
    full = [("-", v, w) for w in outs] + \
        [("+", v, w) for w in news[:dd - len(outs)]]
    check(full)
    assert ds.rebuilds == 1


def test_snapshot_upload_does_not_grow_with_n():
    """After the first (rebuild) step, the bytes a step sends up depend on
    the batch alone: the same batch over 1,000 and 100,000 vertices."""
    g0, batches = edge_stream(n=1000, m_init=4000, steps=3, batch=60,
                              seed=5)
    floors = stream_width_floors(g0, batches)
    sent = {}
    for n in (1000, 100_000):
        store = SnapshotStore(DiGraph.from_edges(n, g0.edges()))
        ds = DeviceSnapshotStore(store, d_min=floors[0],
                                 delta_d_min=floors[1])
        for t, batch in enumerate(batches):
            with obs.span("timestep", key=("h2d", n, t)):
                store.begin_step(batch)
                ds.step_snapshot()
                store.end_step()
            sent[n, t] = obs.counters(key=("h2d", n, t))["snapshot.h2d_bytes"]
        assert ds.rebuilds == 1
    assert sent[100_000, 0] > sent[1000, 0]         # the rebuild is O(N)
    for t in (1, 2):
        assert sent[100_000, t] == sent[1000, t]


# --------------------------------------------------------------------------
# engine: one compiled ΔP_i enumerator vs the snapshot diff
# --------------------------------------------------------------------------


def test_single_plan_enumerator_counts():
    import jax
    from repro.core.engine_sbenu_jax import (build_sbenu_enumerator,
                                             device_put_snapshot,
                                             plan_level_count)
    p = get_pattern("dtoy")
    g0, batches = edge_stream(n=20, m_init=80, steps=1, batch=15, seed=3)
    store = SnapshotStore(g0)
    plans = generate_best_sbenu_plans(p, GraphStats(20, 80, delta_edges=15))
    want_p, want_m = snapshot_diff_oracle(p, store, batches[0])
    store.begin_step(batches[0])
    snap = device_put_snapshot(store.device_snapshot())
    starts = np.asarray(store.start_vertices(), np.int32)
    valid = np.ones(starts.shape[0], bool)
    got_p, got_m = set(), set()
    for plan in plans:
        caps = [256] * plan_level_count(plan)
        run = jax.jit(build_sbenu_enumerator(plan, store.n, caps,
                                             collect_matches=True))
        res = run(snap, starts, valid)
        assert int(res.overflow) == 0
        mv = np.asarray(res.matches_valid)
        rows = np.asarray(res.matches)[mv]
        ops = np.asarray(res.match_ops)[mv]
        for row, o in zip(rows, ops):
            (got_p if o > 0 else got_m).add(tuple(int(x) for x in row))
    store.end_step()
    assert got_p == want_p
    assert got_m == want_m


def test_level_fanout_hints():
    from repro.core.engine_sbenu_jax import sbenu_level_fanouts
    stats = GraphStats(1000, 10000, delta_edges=100)
    # directed 4-cycle: the f3 level enumerates a single typed adjacency
    plans = generate_best_sbenu_plans(get_pattern("q2'"), stats)
    assert any(any(f) for f in map(sbenu_level_fanouts, plans))
    # directed triangle: every level intersects >= 2 adjacencies
    plans = generate_best_sbenu_plans(get_pattern("q1'"), stats)
    assert all(not any(f) for f in map(sbenu_level_fanouts, plans))


def test_sbenu_plans_reject_static_engine():
    """The static engine must keep refusing S-BENU plans (they route to
    engine_sbenu_jax instead)."""
    from repro.core.engine_jax import check_jit_supported
    plans = generate_best_sbenu_plans(get_pattern("q1'"),
                                      GraphStats(100, 500, delta_edges=10))
    with pytest.raises(NotImplementedError):
        check_jit_supported(plans[0])


# --------------------------------------------------------------------------
# storage: mesh-sharded six-block store vs a fresh host build (in-process
# single-device mesh — the 8-way layout is covered by the slow conformance
# matrix in test_conformance.py)
# --------------------------------------------------------------------------


def test_sharded_snapshot_store_matches_host_build():
    import jax
    from jax.sharding import Mesh
    from repro.graph.dynamic import ShardedDeviceSnapshotStore

    g0, batches = edge_stream(n=30, m_init=140, steps=3, batch=25, seed=9)
    store = SnapshotStore(g0)
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    ds = ShardedDeviceSnapshotStore.for_store(store, mesh, hot=4)
    assert ShardedDeviceSnapshotStore.for_store(store, mesh, hot=4) is ds
    # a plain device mirror with "the same" layout params must NOT alias
    # the sharded one (their params tuples differ by construction)
    assert DeviceSnapshotStore.for_store(store) is not ds
    n = store.n
    for batch in batches:
        store.begin_step(batch)
        blocks, hot, spec = ds.step_sharded()
        want = store.device_snapshot()
        assert spec.n_shards * spec.rows_per_shard \
            == np.asarray(blocks["prev_out"]).shape[0]
        for name, wrows in (("prev_out", want.prev_out),
                            ("cur_out", want.cur_out),
                            ("prev_in", want.prev_in),
                            ("cur_in", want.cur_in)):
            got = np.asarray(blocks[name])
            for v in range(n):
                assert _row_set(got, v, n) == _row_set(wrows, v, n), \
                    (name, v)
            # hot slice = the top-id rows + the sentinel row, replicated
            hrows = np.asarray(hot[name])
            assert hrows.shape[0] == spec.hot + 1
            assert (hrows == got[n - spec.hot:n + 1]).all()
        # joint delta block round-trips values and signs
        dj = np.asarray(blocks["delta_joint_out"])
        dd = dj.shape[1] // 2
        for v in range(n):
            plus = {int(x) for x, s in zip(dj[v, :dd], dj[v, dd:])
                    if s == 1}
            minus = {int(x) for x, s in zip(dj[v, :dd], dj[v, dd:])
                     if s == -1}
            assert plus == set(store.get_adj(v, "delta", "out", "+")), v
            assert minus == set(store.get_adj(v, "delta", "out", "-")), v
        store.end_step()
    assert ds.rebuilds >= 1


def test_sbenu_snapshot_partition_specs_match_engine_layout():
    """The published specs (launch/shardings.py) must spell exactly the
    layout build_sbenu_dist_step's in_specs consume: value blocks
    row-partitioned, hot slices + starts as the engine expects."""
    from jax.sharding import PartitionSpec as P
    from repro.core.engine_sbenu_dist import BLOCK_ORDER
    from repro.launch.shardings import batch_specs, sbenu_snapshot_specs

    specs = sbenu_snapshot_specs("shard")
    assert len(specs) == 2 * len(BLOCK_ORDER) + 2
    for name in BLOCK_ORDER:
        assert specs[name] == P("shard", None), name
        assert specs[f"hot_{name}"] == P(None, None), name
    assert specs["starts"] == P("shard")
    assert specs["starts_valid"] == P("shard")
    # the dry-run kind routes to the same specs (flattened mesh axes)
    via_kind = batch_specs("benu", "sbenu_dist_enum", {}, False)
    assert via_kind["prev_out"] == P(("data", "model"), None)
