"""Kernel dispatch + autotune layer: one registry for every kernels/ op.

Before this module, each public op in :mod:`repro.kernels.ops` carried its
own copy of the dispatch policy — an ``_on_tpu()`` probe here, a
``REPRO_INTERSECT_IMPL`` read there, a third copy of the mixed-width
operand padding in the streaming engine. This module centralizes all of
it:

* **impl resolution** (:func:`resolve_impl`) — one order for every op:
  an explicit ``impl=`` argument always wins; ``auto`` consults the op's
  environment override (``REPRO_<OP>_IMPL``, e.g. ``REPRO_INTERSECT_IMPL``
  — the CI hook that forces the Pallas path in interpret mode on the CPU
  container); otherwise the registry's platform × width default applies.
* **tile selection** (:func:`pick_tiles`) — the ``(bm, bk)`` table per
  op and platform (``bm`` rows per block along the
  batch axis, ``bk`` lanes per chunk along the set-width axis), with
  per-call overrides, clamped so ``bk`` divides the padded width and
  ``bm`` divides the batch.
* **operand padding** (:func:`pad_operands`) — the mixed-width padding
  the Pallas kernels need (both operands to a common lane width, batch to
  a ``bm`` multiple, holes sentinel-filled so padding never adds set
  members), previously duplicated at three call sites.

See ``docs/KERNELS.md`` for the kernel inventory and the "how to add a
kernel" recipe built on :func:`register_op`.

Example — the resolution order, end to end::

    >>> import os
    >>> from repro.kernels import dispatch
    >>> _ = os.environ.pop("REPRO_INTERSECT_IMPL", None)   # clean slate
    >>> dispatch.resolve_impl("intersect", "pallas-interpret")  # alias
    'interpret'
    >>> dispatch.resolve_impl("intersect", "auto", platform="tpu")
    'pallas'
    >>> dispatch.resolve_impl("intersect", "auto", platform="cpu", width=64)
    'ref'
    >>> dispatch.resolve_impl("intersect", "auto", platform="cpu",
    ...                       width=1024)                  # wide rows: O(D)
    'chunked'
    >>> os.environ["REPRO_INTERSECT_IMPL"] = "pallas-interpret"
    >>> dispatch.resolve_impl("intersect")         # env overrides 'auto' ...
    'interpret'
    >>> dispatch.resolve_impl("intersect", "binary")  # ... explicit wins
    'binary'
    >>> _ = os.environ.pop("REPRO_INTERSECT_IMPL", None)
    >>> dispatch.pick_tiles("intersect", batch=64, width=256)
    (8, 128)
    >>> # odd width: bk falls back to the full row (callers pad batch to bm)
    >>> dispatch.pick_tiles("intersect", batch=7, width=200)
    (8, 200)
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

#: spellings accepted everywhere an ``impl=`` is taken (CLI, env, code)
IMPL_ALIASES = {"pallas-interpret": "interpret"}

#: a platform default: an impl name, or a callable ``width -> impl name``
#: (``width`` may be None when the caller has no shape at hand)
Default = Union[str, Callable[[Optional[int]], str]]


@dataclass(frozen=True)
class OpSpec:
    """Registry entry: the impls an op accepts + its platform defaults."""

    name: str
    impls: Tuple[str, ...]
    defaults: Dict[str, Default]         # platform ('*' fallback) -> Default
    env: str                             # environment override variable


_OPS: Dict[str, OpSpec] = {}


def register_op(name: str, impls: Tuple[str, ...],
                defaults: Dict[str, Default],
                env: Optional[str] = None) -> OpSpec:
    """Register a kernel op with the dispatcher.

    ``impls`` are the accepted ``impl=`` names (``auto`` and the
    ``pallas-interpret`` alias are implicit). ``defaults`` maps platform
    names (``jax.default_backend()`` values; ``'*'`` as fallback) to an
    impl name or a ``width -> impl`` callable. ``env`` defaults to
    ``REPRO_<NAME>_IMPL``.
    """
    spec = OpSpec(name=name, impls=tuple(impls), defaults=dict(defaults),
                  env=env or f"REPRO_{name.upper()}_IMPL")
    _OPS[name] = spec
    return spec


def op_spec(op: str) -> OpSpec:
    try:
        return _OPS[op]
    except KeyError:
        raise ValueError(f"unknown kernel op {op!r}; registered: "
                         f"{sorted(_OPS)}") from None


def _normalize(spec: OpSpec, impl: str) -> str:
    impl = IMPL_ALIASES.get(impl, impl)
    if impl != "auto" and impl not in spec.impls:
        raise ValueError(
            f"{spec.name}: unknown impl {impl!r}; choose from "
            f"{('auto',) + spec.impls} (or alias "
            f"{sorted(IMPL_ALIASES)})")
    return impl


def resolve_impl(op: str, impl: str = "auto",
                 platform: Optional[str] = None,
                 width: Optional[int] = None) -> str:
    """Resolve ``impl`` for ``op``: explicit > env override > registry.

    The single resolution order every public op follows (the bug class
    this kills: ops that read the env but ignored an explicit argument,
    or probed the platform but ignored the env). ``platform`` defaults to
    ``jax.default_backend()``; ``width`` feeds width-dependent defaults
    (e.g. the CPU intersect switches to the O(D)-memory chunked scan on
    wide rows).
    """
    spec = op_spec(op)
    impl = _normalize(spec, impl)
    if impl != "auto":
        return impl
    env_val = os.environ.get(spec.env, "").strip()
    if env_val:
        resolved = _normalize(spec, env_val)
        if resolved != "auto":
            return resolved
    platform = platform or jax.default_backend()
    default = spec.defaults.get(platform, spec.defaults["*"])
    if callable(default):
        default = default(width)
    return _normalize(spec, default)


# --------------------------------------------------------------------------
# Tile-size table (the autotune layer)
# --------------------------------------------------------------------------

#: (bm, bk) per op x platform, bucketed by set width:
#: ``(max_width_inclusive | None, bm, bk)`` rows, first match wins. The TPU
#: rows are compile-checked, not benchmarked: tests/test_tpu_compile.py
#: compiles both kernels for a v5e at the tilings these rows give (widths
#: 128..4096). ``bm`` is a multiple of 8 (a block's second-to-last
#: dimension must be) and ``bk`` = 128 keeps the compare working set at
#: ``bm * bk * 128`` int32 whatever the row width (budget math in
#: kernels/sorted_intersect.py). Chip sweeps to refill them are ROADMAP A7.
#: The ``'*'`` rows were measured with ``benchmarks/roofline.py --fused``
#: in interpret mode on a 2-core CPU container (wider bk only pays off
#: once rows exceed ~1k lanes). Override per call via pick_tiles(bm=, bk=).
TILE_TABLE: Dict[str, Dict[str, Tuple[Tuple[Optional[int], int, int], ...]]] = {
    "intersect": {
        "tpu": ((None, 8, 128),),
        "*": ((None, 8, 128),),
    },
    "gather_intersect": {
        "tpu": ((None, 8, 128),),
        "gpu": ((None, 16, 128),),
        "*": ((None, 8, 128),),
    },
}


def pick_tiles(op: str, batch: int, width: int,
               platform: Optional[str] = None,
               bm: Optional[int] = None,
               bk: Optional[int] = None) -> Tuple[int, int]:
    """``(bm, bk)`` for a ``[batch, width]`` problem on ``platform``.

    Units: ``bm`` counts frontier rows per kernel block (batch axis);
    ``bk`` counts int32 lanes per inner-loop chunk (set-width axis).
    Explicit ``bm``/``bk`` are taken verbatim except for the width clamp:
    ``bk`` must divide ``width`` (falls back to 128 | width, then
    ``width`` itself). ``bm`` is returned as-is from the table — the
    kernels require ``batch % bm == 0``, and every ops.py wrapper pads
    the batch up to a ``bm`` multiple *after* picking tiles
    (:func:`pad_operands` / :func:`pad_to_multiple`; a handful of
    sentinel rows beats shrinking the block to ``bm=1`` and multiplying
    the grid steps).
    """
    table = TILE_TABLE[op]
    rows = table.get(platform or jax.default_backend(), table["*"])
    tbm, tbk = rows[-1][1:]
    for wmax, rbm, rbk in rows:
        if wmax is None or width <= wmax:
            tbm, tbk = rbm, rbk
            break
    bm = bm if bm is not None else tbm
    bk = bk if bk is not None else tbk
    if width % bk != 0:
        bk = 128 if width % 128 == 0 else width
    return bm, bk


# --------------------------------------------------------------------------
# Shared operand padding (mixed widths, batch multiples)
# --------------------------------------------------------------------------


def pad_to(x: jax.Array, axis: int, size: int, fill) -> jax.Array:
    """Pad ``x`` along ``axis`` up to ``size`` entries with ``fill``."""
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def pad_to_multiple(x: jax.Array, axis: int, multiple: int,
                    fill) -> jax.Array:
    """Pad ``x`` along ``axis`` up to the next ``multiple`` with ``fill``."""
    size = x.shape[axis]
    return pad_to(x, axis, size + ((-size) % multiple), fill)


def pad_operands(a: jax.Array, b: jax.Array, sentinel: int,
                 bm: int) -> Tuple[jax.Array, jax.Array]:
    """Pad a mixed-width operand pair for a row-blocked Pallas kernel.

    Both rows are padded to the wider width and the batch to a ``bm``
    multiple; every hole is sentinel-valued, so padding never adds set
    members (the padded-set invariant of kernels/ref.py). This is the one
    copy of the logic previously repeated in ops.py's Pallas branch, the
    streaming engine's impl resolver, and the width-matching fetch.
    """
    w = max(a.shape[-1], b.shape[-1])
    ap = pad_to_multiple(pad_to(a, 1, w, sentinel), 0, bm, sentinel)
    bp = pad_to_multiple(pad_to(b, 1, w, sentinel), 0, bm, sentinel)
    return ap, bp


# --------------------------------------------------------------------------
# Fused-fetch toggle (engine-level, not per-op)
# --------------------------------------------------------------------------


def fused_fetch_enabled(default: bool = False) -> bool:
    """Whether engines should fuse DBQ gathers into the intersect kernel.

    ``REPRO_FUSED_FETCH`` forces it on (``1``/``on``/``true``) or off
    (``0``/``off``/``false``) for the static frontier backends (``jax`` /
    ``jax-gpu`` — currently the only consumers; the streaming and OOC
    engines have no device-resident adjacency gather to fuse yet, see
    the ROADMAP follow-ups) — the CI hook that runs the fast tier-1
    profile through the fused path. Unset, ``default`` applies (True for
    the ``jax-gpu`` backend, False elsewhere).
    """
    val = os.environ.get("REPRO_FUSED_FETCH", "").strip().lower()
    if val in ("1", "on", "true", "yes"):
        return True
    if val in ("0", "off", "false", "no"):
        return False
    return default


# --------------------------------------------------------------------------
# The built-in ops (kernels/ops.py maps these names to callables)
# --------------------------------------------------------------------------


def _cpu_intersect_default(width: Optional[int]) -> str:
    # wide rows: the O(D)-memory chunked scan; narrow: the dense probe
    return "chunked" if (width or 0) > 512 else "ref"


register_op("intersect",
            impls=("ref", "chunked", "binary", "pallas", "interpret"),
            defaults={"tpu": "pallas", "*": _cpu_intersect_default},
            env="REPRO_INTERSECT_IMPL")
register_op("gather_intersect",
            impls=("ref", "chunked", "binary", "pallas", "interpret"),
            defaults={"tpu": "pallas", "gpu": "pallas", "*": "ref"})
register_op("flash_attention",
            impls=("ref", "pallas", "interpret"),
            defaults={"tpu": "pallas", "*": "ref"})
register_op("rmsnorm",
            impls=("ref", "pallas", "interpret"),
            defaults={"tpu": "pallas", "*": "ref"})
