"""Pallas TPU kernel: row-wise padded-set intersection.

The INT instruction is BENU's compute hot-spot — the paper's computation-cost
model literally counts INT executions (§4.3.1). On TPU we realize a batch of
INT instructions (one frontier level) as one kernel launch over the frontier.

Design (TPU-native, not a CUDA port)
------------------------------------
Membership of each ``a`` element in the row's ``b`` set is tested with a
block-broadcast compare matrix — a dense ``[bm, bk, bk]`` equality reduce
that maps onto the VPU (8x128 vector lanes); sorted-merge / binary-search
variants are serial and branchy, hostile to the TPU's SIMD model. Rows are
lane-aligned (callers pad ``D`` to a multiple of 128). Both operands are
consumed in ``bk``-wide chunks from VMEM: the outer loop walks the ``a``
chunks, the inner loop scans every ``b`` chunk and OR-accumulates an int32
membership mask, and each finished ``a`` chunk is written straight into the
output block. A chunk that holds only holes on either side skips its
compares: rows are padded to the widest row of the graph (a hub's degree),
so most chunks of a typical row are padding. Output keeps matching ``a``
entries in place (holes = sentinel), so results remain valid padded sets
with no compaction step.

Mosaic constraints that shaped the loops: a ``fori_loop`` may not carry a
bool vector (``scf.for`` fails to legalize), so the mask carry is int32;
chunk offsets are ``pl.multiple_of(k * bk, bk)`` so every slice is
lane-aligned.

VMEM per block (int32): the ``a``, ``b`` and output blocks are ``bm * D``
each, double-buffered by the pipeline (6 x 64 KiB at bm=8, D=2048); the
compare working set is ``bm * bk * bk`` (512 KiB at bm=8, bk=128) and does
not grow with ``D``, so every width up to the row cap fits the 16 MiB
scoped VMEM of a v5e core.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _has_members(x: jax.Array, sentinel: int) -> jax.Array:
    """Scalar: whether a lane chunk holds any entry that is not a hole."""
    return jnp.max(jnp.where(x != sentinel, 1, 0)) > 0


def probe_block(a_ref, o_ref, b_chunk: Callable[[jax.Array], jax.Array],
                nb: int, *, sentinel: int, bk: int) -> None:
    """``o = where(a ∈ b, a, sentinel)`` row-wise over one VMEM block.

    ``a_ref``/``o_ref``: ``[bm, Da]`` with ``Da`` a multiple of ``bk``;
    ``b_chunk(k)`` loads the ``k``-th of ``nb`` lane chunks of the ``b``
    rows (``[bm, kb]``, any lane-aligned ``kb``). Shared by this kernel and
    the fused gather (kernels/gather_intersect.py), whose ``b`` rows are
    the adjacency rows it DMA'd into VMEM. A chunk that holds only holes,
    on either side, skips its compares: it cannot add members.
    """
    na = a_ref.shape[-1] // bk

    def a_chunk(ja, carry):
        ao = pl.multiple_of(ja * bk, bk)
        a = a_ref[:, pl.ds(ao, bk)]                      # [bm, bk]

        def probe(member, b):
            eq = a[:, :, None] == b[:, None, :]          # [bm, bk, kb]
            return member | jnp.any(eq, axis=-1).astype(jnp.int32)

        def scan_b(kb, member):
            b = b_chunk(kb)                              # [bm, kb]
            return jax.lax.cond(_has_members(b, sentinel),
                                lambda m: probe(m, b), lambda m: m, member)

        live = _has_members(a, sentinel)

        @pl.when(live)
        def _probe():
            member = jax.lax.fori_loop(0, nb, scan_b,
                                       jnp.zeros(a.shape, jnp.int32))
            o_ref[:, pl.ds(ao, bk)] = jnp.where(
                (a != sentinel) & (member != 0), a, sentinel)

        @pl.when(jnp.logical_not(live))
        def _holes():
            o_ref[:, pl.ds(ao, bk)] = a
        return carry

    jax.lax.fori_loop(0, na, a_chunk, 0)


def _intersect_kernel(a_ref, b_ref, o_ref, *, sentinel: int, bk: int):
    def b_chunk(kb):
        return b_ref[:, pl.ds(pl.multiple_of(kb * bk, bk), bk)]

    probe_block(a_ref, o_ref, b_chunk, b_ref.shape[-1] // bk,
                sentinel=sentinel, bk=bk)


@functools.partial(jax.jit, static_argnames=("sentinel", "bm", "bk",
                                             "interpret"))
def sorted_intersect_pallas(a: jax.Array, b: jax.Array, sentinel: int,
                            bm: int = 8, bk: int = 128,
                            interpret: bool = False) -> jax.Array:
    """``a ∩ b`` per row for padded sets. a, b: int32[B, D] -> int32[B, D].

    ``D`` must be a multiple of ``bk`` and ``B`` a multiple of ``bm``
    (callers pad; see ops.intersect_padded).
    """
    B, D = a.shape
    assert b.shape == (B, D), (a.shape, b.shape)
    assert D % bk == 0, f"D={D} not a multiple of bk={bk}"
    assert B % bm == 0, f"B={B} not a multiple of bm={bm}"
    return pl.pallas_call(
        functools.partial(_intersect_kernel, sentinel=sentinel, bk=bk),
        grid=(B // bm,),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i: (i, 0)),
            pl.BlockSpec((bm, D), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), a.dtype),
        interpret=interpret,
        name="sorted_intersect_pallas",
    )(a, b)
