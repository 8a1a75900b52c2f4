"""Compile the main path's kernels for a TPU v5e that is described, not
attached: what Mosaic or XLA would refuse on the chip fails here, at no
chip time (nothing runs, so results are the interpret-mode tests' job).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Keep these tests in this one file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, ops

N = 300_000          # the smoke graph: powerlaw(300_000, 4), rows 2176 wide


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", [128, 512, 2176, 4096])
def test_sorted_intersect_compiles(one_chip, width):
    bm, bk = dispatch.pick_tiles("intersect", 1024, width, platform="tpu")
    assert bm % 8 == 0 and width % bk == 0
    fn = jax.jit(lambda a, b: ops.intersect_padded(
        a, b, N, impl="pallas", bm=bm, bk=bk))
    spec = _spec(one_chip, (1024, width))
    _assert_kernel(fn.lower(spec, spec).compile())


@pytest.mark.parametrize("width", [2176, 4096])
def test_gather_intersect_compiles(one_chip, width):
    batch = 16384
    bm, bk = dispatch.pick_tiles("gather_intersect", batch, width,
                                 platform="tpu")
    assert bm % 8 == 0 and width % bk == 0
    fn = jax.jit(lambda ids, cand, lanes: ops.fused_gather_intersect(
        cand, ids, lanes, N, impl="pallas", bm=bm, bk=bk))
    compiled = fn.lower(
        _spec(one_chip, (batch,)), _spec(one_chip, (batch, width)),
        _spec(one_chip, ((N + 1) * width // 128, 128))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("fused", [False, True], ids=["jax", "jax-gpu"])
def test_triangle_chunk_program_compiles(one_chip, fused):
    """One jitted chunk of the static engine, as JaxBackend builds it."""
    from repro.core.engine_jax import build_enumerator, row_fetch
    from repro.core.estimate import GraphStats
    from repro.core.pattern import get_pattern
    from repro.core.plangen import generate_best_plan
    width = 2176
    plan = generate_best_plan(get_pattern("triangle"),
                              GraphStats(N, 1_199_984))

    def chunk(rows, lanes, starts, valid):
        run = build_enumerator(
            plan, N, (256, 256), row_fetch(rows, N),
            intersect_impl="pallas", gather_intersect_impl="pallas",
            fused_rows=lanes if fused else None)
        res = run(starts, valid)
        return res.count, res.overflow

    compiled = jax.jit(chunk).lower(
        _spec(one_chip, (N + 1, width)),
        _spec(one_chip, ((N + 1) * width // 128, 128)),
        _spec(one_chip, (64,)), _spec(one_chip, (64,), jnp.bool_)).compile()
    _assert_kernel(compiled)


def test_enu_compaction_has_no_scatter(one_chip):
    """ENU compaction at the census's level-2 shape (16,384 rows of 4,096
    candidates into 24,576 child slots) searches the prefix sums: no
    scatter of the B x D candidates, which the TPU serializes."""
    from repro.core.engine_jax import _expand
    B, D, cap = 16384, 4096, 24576
    u, c = ("A", 0), ("A", 1)

    def compact(col, valid, cand):
        return _expand({u: col}, valid, cand, c, cap, frozenset({u}), N)

    compiled = jax.jit(compact).lower(
        _spec(one_chip, (B,)), _spec(one_chip, (B,), jnp.bool_),
        _spec(one_chip, (B, D))).compile()
    text = compiled.as_text()
    assert "gather(" in text
    assert "scatter(" not in text
