"""jit'd public wrappers around the Pallas kernels with jnp fallbacks.

All callers in the model/engine code go through this module so the
implementation can be swapped per-backend without touching call sites.
Dispatch is owned by :mod:`repro.kernels.dispatch` — one resolution order
for every op (explicit ``impl=`` argument > ``REPRO_<OP>_IMPL``
environment override > platform × width registry default), one tile-size
table, one mixed-width operand-padding helper. ``pallas-interpret`` (as
an argument or an env value) runs the Pallas kernel in interpret mode on
any backend — the CI hook that keeps the TPU/GPU kernel paths
conformance-tested on the CPU container. See ``docs/KERNELS.md`` for the
kernel inventory and tiling knobs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import dispatch, ref
from .flash_attention import flash_attention_pallas
from .gather_intersect import LANE, gather_intersect_pallas, lane_rows
from .rmsnorm import rmsnorm_pallas
from .sorted_intersect import sorted_intersect_pallas


# --------------------------------------------------------------------------
# intersect
# --------------------------------------------------------------------------


def _check_binary_operands(a: jax.Array, b: jax.Array, sentinel: int) -> None:
    """Loud precondition check for ``impl='binary'``.

    The binary-search probe requires 2-D operands with matching batch and
    ``b`` rows *fully ascending* with holes only in the tail (fresh DBQ
    rows are; INT results carry in-place holes — keep those on the ``a``
    side, or resort; see kernels/ref.py). Violations used to surface as
    an opaque vmap/searchsorted shape error or, worse, silently wrong
    memberships; now they raise a ValueError up front. The sortedness
    check only runs on concrete (non-traced) arrays — inside jit the
    caller's invariant is trusted.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[0] != b.shape[0]:
        raise ValueError(
            "impl='binary' needs 2-D operands with a shared batch: got "
            f"a{tuple(a.shape)}, b{tuple(b.shape)}; pad/stack rows first "
            "(dispatch.pad_operands) or use impl='ref'")
    if not isinstance(b, jax.core.Tracer):
        rows = jnp.asarray(b)
        if rows.size and bool(jnp.any(rows[:, 1:] < rows[:, :-1])):
            raise ValueError(
                "impl='binary' needs b rows fully ascending with holes "
                "only in the tail (sentinel-padded DBQ rows); this b has "
                "out-of-order entries or interspersed holes — resort "
                "(jnp.sort(b, axis=-1)) or use impl='ref'/'chunked'")


def intersect_padded(a: jax.Array, b: jax.Array, sentinel: int,
                     impl: str = "auto", bm: Optional[int] = None,
                     bk: Optional[int] = None) -> jax.Array:
    """Row-wise padded-set intersection; see kernels/ref.py for semantics.

    a: int32[B, Da], b: int32[B, Db] (widths may differ — the Pallas path
    pads both operands to the wider width, rounded up to 128 lanes, via
    dispatch.pad_operands; holes are sentinel-valued so padding never adds
    members). ``impl``:
    auto | pallas | ref | chunked | binary | interpret (alias
    ``pallas-interpret``); resolution order in kernels/dispatch.py.
    ``binary`` needs ``b`` rows fully ascending (holes only in the tail)
    and raises ValueError on concrete violations. ``bm``/``bk`` override
    the tile table (rows per block / lanes per chunk).
    """
    impl = dispatch.resolve_impl("intersect", impl, width=a.shape[-1])
    if impl == "ref":
        return ref.sorted_intersect(a, b, sentinel)
    if impl == "chunked":
        return ref.sorted_intersect_chunked(a, b, sentinel)
    if impl == "binary":
        _check_binary_operands(a, b, sentinel)
        return ref.sorted_intersect_binary(a, b, sentinel)
    interpret = impl == "interpret"
    B, Da = a.shape
    W = _lane_width(max(Da, b.shape[1]))
    bm, bk = dispatch.pick_tiles("intersect", B, W, bm=bm, bk=bk)
    ap, bp = dispatch.pad_operands(dispatch.pad_to(a, 1, W, sentinel), b,
                                   sentinel, bm)
    out = sorted_intersect_pallas(ap, bp, sentinel, bm=bm, bk=bk,
                                  interpret=interpret)
    return out[:B, :Da]


def _lane_width(width: int) -> int:
    """``width`` rounded up to whole 128-lane vregs (the Pallas paths)."""
    return -(-width // LANE) * LANE


# --------------------------------------------------------------------------
# fused gather + intersect (the GPU/TPU fetch path)
# --------------------------------------------------------------------------


def fused_gather_intersect(cand: jax.Array, ids: jax.Array,
                           rows: jax.Array, sentinel: int,
                           impl: str = "auto", bm: Optional[int] = None,
                           bk: Optional[int] = None) -> jax.Array:
    """``cand[i] ∩ rows[ids[i]]`` without materializing ``rows[ids]``.

    The DBQ-level gather and the candidate-set intersection in one kernel
    launch: cand int32[B, Dc] padded sets, ids int32[B] frontier row
    indices (any values — clipped to the sentinel row), rows int32[N+1, D]
    padded adjacency whose row N is all-sentinel — or the same table as
    :func:`~repro.kernels.gather_intersect.lane_rows`, the layout the
    kernel DMAs from (engines pass that, built once per graph, so no call
    relayouts the table). Returns int32[B, Dc] in ``cand``'s slots —
    bit-equal to ``intersect_padded(cand, rows[clip(ids)], sentinel)``.

    ``impl``: auto | pallas | interpret fuse on device
    (kernels/gather_intersect.py); ref | chunked | binary fall back to
    gather-then-intersect with that intersect impl (the unfused reference
    the property tests compare against). ``auto`` resolves via the
    dispatch registry (``REPRO_GATHER_INTERSECT_IMPL`` env override;
    pallas on tpu/gpu, ref elsewhere).
    """
    impl = dispatch.resolve_impl("gather_intersect", impl,
                                 width=rows.shape[-1])
    ids = jnp.clip(ids, 0, sentinel)
    n_rows = sentinel + 1
    if impl in ("ref", "chunked", "binary"):
        adj = rows.reshape(n_rows, -1)       # either layout -> [N+1, D]
        return intersect_padded(cand, adj[ids], sentinel, impl=impl)
    interpret = impl == "interpret"
    lanes = lane_rows(rows, sentinel) if rows.shape[0] == n_rows else rows
    B, Dc = cand.shape
    W = _lane_width(Dc)
    bm, bk = dispatch.pick_tiles("gather_intersect", B, W, bm=bm, bk=bk)
    cp = dispatch.pad_to_multiple(dispatch.pad_to(cand, 1, W, sentinel), 0,
                                  bm, sentinel)
    ip = dispatch.pad_to_multiple(ids, 0, bm, sentinel)
    out = gather_intersect_pallas(ip, cp, lanes, sentinel, bm=bm, bk=bk,
                                  interpret=interpret)
    return out[:B, :Dc]


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    impl: str = "auto") -> jax.Array:
    """q: [B, Hq, Tq, d]; k, v: [B, Hkv, Tk, d] -> [B, Hq, Tq, d].

    ``auto`` resolves via the dispatch registry (explicit impl >
    ``REPRO_FLASH_ATTENTION_IMPL`` > pallas on TPU, ref elsewhere).
    """
    impl = dispatch.resolve_impl("flash_attention", impl)
    if impl == "ref":
        return ref.flash_attention(q, k, v, causal=causal, scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  interpret=(impl == "interpret"))


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6,
            impl: str = "auto") -> jax.Array:
    """RMSNorm over the last axis; arbitrary leading dims.

    ``auto`` resolves via the dispatch registry (explicit impl >
    ``REPRO_RMSNORM_IMPL`` > pallas on TPU, ref elsewhere).
    """
    impl = dispatch.resolve_impl("rmsnorm", impl)
    if impl == "ref":
        return ref.rmsnorm(x, gamma, eps)
    shape = x.shape
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, shape[-1])
    bm = 256
    while rows % bm != 0:
        bm //= 2
    out = rmsnorm_pallas(x2, gamma, eps=eps, bm=max(bm, 1),
                         interpret=(impl == "interpret"))
    return out.reshape(shape)
