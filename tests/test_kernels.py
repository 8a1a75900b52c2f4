"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles in kernels/ref.py (+ hypothesis property tests on the
padded-set algebra)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need the hypothesis dev dep")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


def _rand_padded_sets(rng, b, d, n):
    rows = np.full((b, d), n, np.int32)
    for i in range(b):
        k = int(rng.integers(0, min(d, n) + 1))
        rows[i, :k] = np.sort(rng.choice(n, size=k, replace=False))
    return rows


class TestSortedIntersect:
    @pytest.mark.parametrize("b,d", [(1, 128), (8, 128), (16, 256),
                                     (5, 384), (32, 512)])
    def test_sweep_vs_ref(self, b, d):
        rng = np.random.default_rng(b * 1000 + d)
        n = 3 * d
        a = jnp.asarray(_rand_padded_sets(rng, b, d, n))
        bb = jnp.asarray(_rand_padded_sets(rng, b, d, n))
        want = ref.sorted_intersect(a, bb, n)
        got = ops.intersect_padded(a, bb, n, impl="interpret")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    @pytest.mark.parametrize("d,bk", [(256, 128), (2048, 256)])
    def test_multichunk_tilings_vs_ref(self, d, bk):
        """Rows spanning several bk chunks on both sides — the tilings the
        TPU compiler refused before the chunk loops were rewritten — with
        in-place holes and hole-only chunks on the a side."""
        rng = np.random.default_rng(d + bk)
        n = 3 * d
        a = _rand_padded_sets(rng, 16, d, n)
        a[::3, ::5] = n                       # in-place holes (INT results)
        a[1, bk:] = n                         # trailing hole-only chunks
        bb = _rand_padded_sets(rng, 16, d, n)
        want = ref.sorted_intersect(jnp.asarray(a), jnp.asarray(bb), n)
        got = ops.intersect_padded(jnp.asarray(a), jnp.asarray(bb), n,
                                   impl="interpret", bm=8, bk=bk)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    @pytest.mark.parametrize("chunk", [32, 128, 200])
    def test_chunked_vs_ref(self, chunk):
        rng = np.random.default_rng(chunk)
        n = 500
        a = jnp.asarray(_rand_padded_sets(rng, 12, 256, n))
        b = jnp.asarray(_rand_padded_sets(rng, 12, 256, n))
        want = ref.sorted_intersect(a, b, n)
        got = ref.sorted_intersect_chunked(a, b, n, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.sets(st.integers(0, 49), max_size=16), min_size=1,
                    max_size=6),
           st.lists(st.sets(st.integers(0, 49), max_size=16), min_size=1,
                    max_size=6))
    def test_property_matches_python_sets(self, sa, sb):
        """Padded intersection == python set intersection, row-wise."""
        rows = max(len(sa), len(sb))
        sa = (sa * rows)[:rows]
        sb = (sb * rows)[:rows]
        n, d = 50, 32
        a = np.full((rows, d), n, np.int32)
        b = np.full((rows, d), n, np.int32)
        for i in range(rows):
            va = sorted(sa[i])[:d]
            vb = sorted(sb[i])[:d]
            a[i, :len(va)] = va
            b[i, :len(vb)] = vb
        out = np.asarray(ref.sorted_intersect(jnp.asarray(a),
                                              jnp.asarray(b), n))
        for i in range(rows):
            got = {int(x) for x in out[i] if x != n}
            assert got == (sa[i] & sb[i])
            # order/positions of surviving entries preserved
            kept = out[i][out[i] != n]
            assert list(kept) == sorted(kept)


class TestRMSNorm:
    @pytest.mark.parametrize("shape", [(4, 128), (3, 7, 256), (1, 512),
                                       (16, 1024)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep_vs_ref(self, shape, dtype):
        rng = np.random.default_rng(hash((shape, str(dtype))) % 2**31)
        x = jnp.asarray(rng.normal(size=shape), dtype)
        g = jnp.asarray(rng.normal(size=shape[-1:]), dtype)
        want = ref.rmsnorm(x, g)
        got = ops.rmsnorm(x, g, impl="interpret")
        tol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(want, np.float32),
                                   np.asarray(got, np.float32),
                                   rtol=tol, atol=tol)


class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,tq,tk,d", [
        (1, 2, 2, 128, 128, 64),
        (2, 4, 2, 128, 256, 64),      # GQA group 2, decode-offset masking
        (1, 8, 1, 256, 256, 128),     # MQA
        (2, 2, 2, 128, 128, 128),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_sweep_vs_ref(self, b, hq, hkv, tq, tk, d, causal):
        rng = np.random.default_rng(b + hq + tq + tk + causal)
        q = jnp.asarray(rng.normal(size=(b, hq, tq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, hkv, tk, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, hkv, tk, d)), jnp.float32)
        want = ref.flash_attention(q, k, v, causal=causal)
        got = ops.flash_attention(q, k, v, causal=causal, impl="interpret")
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
        want = ref.flash_attention(q, k, v)
        got = ops.flash_attention(q, k, v, impl="interpret")
        np.testing.assert_allclose(np.asarray(want, np.float32),
                                   np.asarray(got, np.float32),
                                   rtol=3e-2, atol=3e-2)


class TestBlockwiseAttention:
    """The jnp flash formulation used by the models on CPU/dry-run."""

    @pytest.mark.parametrize("tq,tk,block", [(64, 64, 16), (64, 128, 32),
                                             (1, 96, 32), (128, 128, 128)])
    def test_vs_ref(self, tq, tk, block):
        from repro.layers.attention import blockwise_attention
        rng = np.random.default_rng(tq + tk + block)
        b, h, d = 2, 3, 32
        q = jnp.asarray(rng.normal(size=(b, tq, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, tk, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, tk, h, d)), jnp.float32)
        got = blockwise_attention(q, k, v, causal=True, block=block)
        want = ref.flash_attention(jnp.moveaxis(q, 2, 1),
                                   jnp.moveaxis(k, 2, 1),
                                   jnp.moveaxis(v, 2, 1), causal=True)
        np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, 2, 1)),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)
