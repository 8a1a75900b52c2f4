"""The GPU fetch path: fused gather+intersect kernel + dispatch registry.

Three layers under test:

* kernels/gather_intersect.py — the fused Pallas kernel must be bit-equal
  to gather-then-``intersect_padded`` (interpret mode on this CPU
  container), including duplicate/sentinel ids and all-sentinel rows —
  the hypothesis property test sweeps exactly those corners;
* kernels/dispatch.py — the one impl-resolution order (explicit > env >
  platform x width registry), the tile table clamps, and the shared
  operand padding;
* the ``jax-gpu`` Executor backend — the fused path behind the unified
  driver stays exact (the full pattern-matrix conformance rows live in
  tests/test_conformance.py).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, ops, ref


def _rand_padded_sets(rng, b, d, n):
    rows = np.full((b, d), n, np.int32)
    for i in range(b):
        k = int(rng.integers(0, min(d, n) + 1))
        rows[i, :k] = np.sort(rng.choice(n, size=k, replace=False))
    return rows


def _rand_adjacency(rng, n, d):
    adj = np.full((n + 1, d), n, np.int32)   # row n = all-sentinel
    for v in range(n):
        k = int(rng.integers(0, min(d, n) + 1))
        adj[v, :k] = np.sort(rng.choice(n, size=k, replace=False))
    return adj


class TestFusedGatherIntersect:
    @pytest.mark.parametrize("b,dc,d", [(1, 128, 128), (8, 128, 128),
                                        (16, 256, 128), (5, 64, 256),
                                        (32, 128, 384)])
    def test_sweep_vs_gather_then_intersect(self, b, dc, d):
        rng = np.random.default_rng(b * 1000 + dc + d)
        n = 2 * d
        adj = _rand_adjacency(rng, n, d)
        cand = _rand_padded_sets(rng, b, dc, n)
        ids = rng.integers(0, n + 1, size=b).astype(np.int32)
        want = ops.intersect_padded(jnp.asarray(cand),
                                    jnp.asarray(adj[np.clip(ids, 0, n)]),
                                    n, impl="ref")
        got = ops.fused_gather_intersect(jnp.asarray(cand),
                                         jnp.asarray(ids),
                                         jnp.asarray(adj), n,
                                         impl="interpret")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_out_of_range_ids_clip_to_sentinel_row(self):
        n, d = 40, 128
        rng = np.random.default_rng(7)
        adj = _rand_adjacency(rng, n, d)
        cand = _rand_padded_sets(rng, 8, d, n)
        ids = np.array([-3, 0, n, n + 99, 1, 2, n, -1], np.int32)
        got = ops.fused_gather_intersect(jnp.asarray(cand),
                                         jnp.asarray(ids),
                                         jnp.asarray(adj), n,
                                         impl="interpret")
        want = ops.intersect_padded(jnp.asarray(cand),
                                    jnp.asarray(adj[np.clip(ids, 0, n)]),
                                    n, impl="ref")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_batches_above_smem_rows_split_into_launches(self,
                                                         monkeypatch):
        """Ids are scalar-prefetched into SMEM (1 MiB on a v5e): larger
        batches run as several launches, bit-equal to one."""
        from repro.kernels import gather_intersect
        monkeypatch.setattr(gather_intersect, "SMEM_ROWS", 16)
        n, d, b = 50, 256, 44                 # 44 rows -> 48 -> 3 launches
        rng = np.random.default_rng(5)
        adj = _rand_adjacency(rng, n, d)
        cand = _rand_padded_sets(rng, b, 128, n)
        ids = rng.integers(0, n + 1, size=b).astype(np.int32)
        got = ops.fused_gather_intersect(jnp.asarray(cand), jnp.asarray(ids),
                                         jnp.asarray(adj), n,
                                         impl="interpret")
        want = ops.intersect_padded(jnp.asarray(cand),
                                    jnp.asarray(adj[ids]), n, impl="ref")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_fallback_impls_match(self):
        """ref/chunked/binary fall back to gather-then-intersect."""
        n, d = 60, 128
        rng = np.random.default_rng(3)
        adj = _rand_adjacency(rng, n, d)
        cand = _rand_padded_sets(rng, 8, d, n)
        ids = rng.integers(0, n + 1, size=8).astype(np.int32)
        outs = [np.asarray(ops.fused_gather_intersect(
            jnp.asarray(cand), jnp.asarray(ids), jnp.asarray(adj), n,
            impl=impl)) for impl in ("ref", "chunked", "binary",
                                     "interpret")]
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)


# the ISSUE's property bar: fused == gather-then-intersect_padded for
# random padded rows including all-sentinel and duplicate-index batches
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 12),
           st.booleans(), st.booleans())
    def test_property_fused_matches_unfused(seed, b, all_sentinel_rows,
                                            duplicate_ids):
        rng = np.random.default_rng(seed)
        n, d, dc = 30, 128, 64
        adj = _rand_adjacency(rng, n, d)
        cand = _rand_padded_sets(rng, b, dc, n)
        if all_sentinel_rows:           # empty candidate sets stay empty
            cand[rng.integers(0, b)] = n
        ids = rng.integers(0, n + 1, size=b).astype(np.int32)
        if duplicate_ids and b > 1:     # same row served to many lanes
            ids[:] = ids[0]
        want = np.asarray(ops.intersect_padded(
            jnp.asarray(cand), jnp.asarray(adj[np.clip(ids, 0, n)]), n,
            impl="ref"))
        got = np.asarray(ops.fused_gather_intersect(
            jnp.asarray(cand), jnp.asarray(ids), jnp.asarray(adj), n,
            impl="interpret"))
        np.testing.assert_array_equal(want, got)
except ImportError:                      # pragma: no cover
    pytestmark_hyp = pytest.mark.skip(
        "property tests need the hypothesis dev dep")

    @pytestmark_hyp
    def test_property_fused_matches_unfused():
        pass


class TestDispatch:
    def test_explicit_impl_always_wins(self, monkeypatch):
        monkeypatch.setenv("REPRO_INTERSECT_IMPL", "pallas-interpret")
        assert dispatch.resolve_impl("intersect", "binary") == "binary"
        assert dispatch.resolve_impl("intersect", "ref") == "ref"
        # aliases normalize wherever they appear
        assert dispatch.resolve_impl("intersect",
                                     "pallas-interpret") == "interpret"

    def test_env_overrides_auto_for_every_op(self, monkeypatch):
        for op, env in (("intersect", "REPRO_INTERSECT_IMPL"),
                        ("gather_intersect",
                         "REPRO_GATHER_INTERSECT_IMPL"),
                        ("flash_attention", "REPRO_FLASH_ATTENTION_IMPL"),
                        ("rmsnorm", "REPRO_RMSNORM_IMPL")):
            monkeypatch.setenv(env, "pallas-interpret")
            assert dispatch.resolve_impl(op) == "interpret", op
            monkeypatch.delenv(env)

    def test_platform_width_defaults(self, monkeypatch):
        monkeypatch.delenv("REPRO_INTERSECT_IMPL", raising=False)
        assert dispatch.resolve_impl("intersect", platform="tpu") == "pallas"
        assert dispatch.resolve_impl("intersect", platform="cpu",
                                     width=64) == "ref"
        assert dispatch.resolve_impl("intersect", platform="cpu",
                                     width=1024) == "chunked"
        monkeypatch.delenv("REPRO_GATHER_INTERSECT_IMPL", raising=False)
        assert dispatch.resolve_impl("gather_intersect",
                                     platform="gpu") == "pallas"
        assert dispatch.resolve_impl("gather_intersect",
                                     platform="cpu") == "ref"

    def test_unknown_op_and_impl_raise(self):
        with pytest.raises(ValueError, match="unknown kernel op"):
            dispatch.resolve_impl("nope")
        with pytest.raises(ValueError, match="unknown impl"):
            dispatch.resolve_impl("intersect", "cuda")

    def test_tile_table_clamps(self):
        # table hit
        assert dispatch.pick_tiles("intersect", 64, 256,
                                   platform="cpu") == (8, 128)
        # bk must divide width; bm stays at the table value — the ops.py
        # wrappers pad the batch up to a bm multiple after picking tiles
        assert dispatch.pick_tiles("intersect", 7, 200,
                                   platform="cpu") == (8, 200)
        # per-call override, still bk-clamped
        assert dispatch.pick_tiles("intersect", 64, 256, platform="cpu",
                                   bm=4, bk=64) == (4, 64)
        assert dispatch.pick_tiles("intersect", 64, 200, platform="cpu",
                                   bk=64) == (8, 200)

    def test_pad_operands_mixed_width(self):
        a = jnp.asarray(np.arange(6, dtype=np.int32).reshape(3, 2))
        b = jnp.asarray(np.arange(12, dtype=np.int32).reshape(3, 4))
        ap, bp = dispatch.pad_operands(a, b, sentinel=99, bm=2)
        assert ap.shape == (4, 4) and bp.shape == (4, 4)
        assert int(ap[0, 3]) == 99 and int(ap[3, 0]) == 99
        np.testing.assert_array_equal(np.asarray(bp[:3]), np.asarray(b))

    def test_fused_fetch_toggle(self, monkeypatch):
        monkeypatch.delenv("REPRO_FUSED_FETCH", raising=False)
        assert dispatch.fused_fetch_enabled() is False
        assert dispatch.fused_fetch_enabled(True) is True
        monkeypatch.setenv("REPRO_FUSED_FETCH", "1")
        assert dispatch.fused_fetch_enabled() is True
        monkeypatch.setenv("REPRO_FUSED_FETCH", "off")
        assert dispatch.fused_fetch_enabled(True) is False


class TestBinaryImplValidation:
    """The ISSUE bugfix: impl='binary' violations raise a clear
    ValueError instead of an opaque vmap/searchsorted shape error (or
    silently wrong memberships)."""

    def test_unsorted_b_raises(self):
        a = jnp.asarray([[1, 2, 9, 9]], jnp.int32)
        b = jnp.asarray([[3, 1, 2, 9]], jnp.int32)     # out of order
        with pytest.raises(ValueError, match="fully ascending"):
            ops.intersect_padded(a, b, 9, impl="binary")

    def test_interspersed_holes_raise(self):
        a = jnp.asarray([[1, 2, 9, 9]], jnp.int32)
        b = jnp.asarray([[1, 9, 2, 9]], jnp.int32)     # hole mid-row
        with pytest.raises(ValueError, match="fully ascending"):
            ops.intersect_padded(a, b, 9, impl="binary")

    def test_shape_violations_raise(self):
        a = jnp.asarray([1, 2, 9], jnp.int32)          # 1-D
        b = jnp.asarray([[1, 2, 9]], jnp.int32)
        with pytest.raises(ValueError, match="2-D operands"):
            ops.intersect_padded(a, b, 9, impl="binary")
        with pytest.raises(ValueError, match="shared batch"):
            ops.intersect_padded(jnp.zeros((2, 4), jnp.int32),
                                 jnp.zeros((3, 4), jnp.int32), 9,
                                 impl="binary")

    def test_valid_operands_still_work_and_jit(self):
        a = jnp.asarray([[0, 2, 5, 9]], jnp.int32)
        b = jnp.asarray([[2, 3, 5, 9]], jnp.int32)
        want = np.asarray(ref.sorted_intersect(a, b, 9))
        got = np.asarray(ops.intersect_padded(a, b, 9, impl="binary"))
        np.testing.assert_array_equal(want, got)
        # under jit the operands are tracers: the invariant is trusted,
        # the check must not trip on them
        jitted = jax.jit(lambda x, y: ops.intersect_padded(
            x, y, 9, impl="binary"))
        np.testing.assert_array_equal(np.asarray(jitted(a, b)), want)


class TestFusedEngineWiring:
    def test_classification_single_use_non_first_only(self):
        from repro.core.engine_jax import classify_fusable_dbqs
        from repro.core.pattern import get_pattern
        from repro.core.plangen import generate_best_plan
        from repro.graph.generate import erdos_renyi
        g = erdos_renyi(64, 256, seed=11)
        plan = generate_best_plan(get_pattern("square"), g.stats())
        fusable = classify_fusable_dbqs(plan)
        dbqs = [i.target for i in plan.instrs if i.op == "DBQ"]
        # square: T5 := Intersect(A1, A3) — A3 (non-first, single-use)
        # fuses, A1 (first operand) stays materialized
        assert dbqs[1] in fusable and dbqs[0] not in fusable

    def test_jax_gpu_backend_quick_conformance(self):
        from repro.core.executor import make_executor
        from repro.core.pattern import get_pattern
        from repro.core.plangen import generate_best_plan
        from repro.graph.generate import powerlaw
        g = powerlaw(48, 4, seed=9)
        plan = generate_best_plan(get_pattern("triangle"), g.stats())
        ref_st = make_executor("ref").run(plan, g, batch=16)
        gpu_st = make_executor(
            "jax-gpu", gather_intersect_impl="interpret").run(
                plan, g, batch=16)
        assert gpu_st.count == ref_st.count
        assert gpu_st.extras["fused_fetch"] is True

    def test_env_can_turn_jax_gpu_fusion_off(self, monkeypatch):
        """REPRO_FUSED_FETCH=0 must be honoured by jax-gpu too (the A/B
        debugging path), not silently ignored."""
        from repro.core.executor import ExecutorConfig, JaxGpuBackend, drive
        from repro.core.pattern import get_pattern
        from repro.core.plangen import generate_best_plan
        from repro.graph.generate import powerlaw
        monkeypatch.setenv("REPRO_FUSED_FETCH", "0")
        g = powerlaw(48, 4, seed=9)
        plan = generate_best_plan(get_pattern("triangle"), g.stats())
        be = JaxGpuBackend()
        st = drive(be, plan, g, ExecutorConfig(batch=16))
        assert be.fused is False
        assert st.extras["fused_fetch"] is False
        from repro.core.executor import make_executor
        assert st.count == make_executor("ref").run(plan, g, batch=16).count

    def test_env_forces_fused_on_plain_jax_backend(self, monkeypatch):
        from repro.core.executor import ExecutorConfig, JaxBackend, drive
        from repro.core.pattern import get_pattern
        from repro.core.plangen import generate_best_plan
        from repro.graph.generate import powerlaw
        monkeypatch.setenv("REPRO_FUSED_FETCH", "1")
        monkeypatch.setenv("REPRO_GATHER_INTERSECT_IMPL",
                           "pallas-interpret")
        g = powerlaw(48, 4, seed=9)
        plan = generate_best_plan(get_pattern("triangle"), g.stats())
        be = JaxBackend()
        st = drive(be, plan, g, ExecutorConfig(batch=16))
        assert be.fused is True
        from repro.core.executor import make_executor
        assert st.count == make_executor("ref").run(plan, g, batch=16).count
