"""Fused Pallas kernel: DBQ-level row gather + padded-set intersection.

The accelerator fetch path of the ROADMAP. BENU's hot loop is
``rows = adjacency[ids]; cand = cand ∩ rows`` — one DBQ gather feeding one
INT per frontier level. Executed separately (engine_jax's unfused path)
the gather materializes a ``[B, D]`` row block in HBM that the intersect
immediately re-reads: 3x the row bytes over the minimum. This kernel fuses
the two: each addressed adjacency row is DMA'd HBM -> VMEM exactly once by
the kernel and consumed from VMEM by the membership probe — the gathered
block never exists in HBM.

Design (TPU-native; CI covers it via ``interpret=True`` on CPU)
---------------------------------------------------------------
The grid walks the frontier in ``bm``-row blocks. ``ids`` ride in SMEM
(``PrefetchScalarGridSpec``) and the candidate/output ``[bm, Dc]`` blocks
are pipelined by ``pallas_call`` as usual. The adjacency stays in HBM
(``memory_space=pl.ANY``) and the kernel gathers it itself: one DMA per
frontier row copies adjacency row ``ids[r]`` (arbitrary order, duplicates
included) into a VMEM scratch, and once all ``bm`` copies have landed the
same chunked membership probe as kernels/sorted_intersect.py
(:func:`~repro.kernels.sorted_intersect.probe_block`) runs against the
candidate block.

The adjacency arrives as **lane rows**: the ``[N+1, D]`` table viewed as
``int32[(N+1) * D/128, 128]`` (:func:`lane_rows`), so adjacency row ``v``
is the ``D/128`` consecutive lane rows from ``v * D/128``. The TPU keeps
a ``[N+1, D]`` array in ``(8, 128)`` tiles, where one row is a strided
run across ``D/128`` tiles, and Mosaic refuses a one-row slice of it
(``Slice shape along dimension 0 must be aligned to tiling (8)``); in
the 128-lane view the tiles are row-major, so a row is one contiguous
copy. The ``k``-th lane chunk of all ``bm`` gathered rows is then one
strided VMEM load (``pl.ds(k, bm, stride=D/128)``). A BlockSpec cannot
express the gather instead: a ``(1, D)`` or squeezed row block breaks
the rule that a block's last two dimensions divide by (8, 128) or equal
the array's.

Sentinel-awareness: ``ids`` must be pre-clipped to ``[0, n]`` (row ``n``
is the all-sentinel row — ops.fused_gather_intersect does this), so a
sentinel id fetches a row with no members and its output row is all
sentinel (an invalid frontier row can never gain members). Holes never
create members: a candidate hole equals the sentinel, which the validity
mask removes regardless of the compare.

Output keeps matching candidate entries **in place** (holes = sentinel),
so results remain valid padded sets — exactly ``intersect_padded(cand,
adjacency[ids])`` bit for bit, which is what tests/test_gpu_fetch.py's
property test pins.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sorted_intersect import probe_block

#: lanes per lane row (the TPU vreg width)
LANE = 128
#: frontier rows per kernel launch: ``ids`` are scalar-prefetched into
#: SMEM, which holds 1 MiB on a v5e (2**18 int32 ids already overflow it)
SMEM_ROWS = 1 << 16


def lane_rows(rows: jax.Array, sentinel: int) -> jax.Array:
    """``int32[N+1, D]`` adjacency -> ``int32[(N+1) * D'/128, 128]``.

    ``D`` is padded with sentinel holes to ``D'``, the next multiple of
    128. The view the fused kernel DMAs rows from; on TPU it is a copy of
    the table (a different tiling), so callers that probe many times keep
    it (``DeviceGraph.lane_rows``) instead of rebuilding it per call.
    """
    d = rows.shape[1]
    pad = -d % LANE
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)), constant_values=sentinel)
    return rows.reshape(-1, LANE)


def _gather_intersect_kernel(ids_ref, cand_ref, adj_hbm, o_ref, rows, sems,
                             *, nc: int, sentinel: int, bk: int):
    bm = o_ref.shape[0]
    base = pl.program_id(0) * bm
    copies = [
        pltpu.make_async_copy(adj_hbm.at[pl.ds(ids_ref[base + r] * nc, nc)],
                              rows.at[pl.ds(r * nc, nc)], sems.at[r])
        for r in range(bm)
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    def b_chunk(k):                    # lane chunk k of every gathered row
        return rows[pl.ds(k, bm, stride=nc), :]          # [bm, 128]

    probe_block(cand_ref, o_ref, b_chunk, nc, sentinel=sentinel, bk=bk)


@functools.partial(jax.jit, static_argnames=("sentinel", "bm", "bk",
                                             "interpret"))
def gather_intersect_pallas(ids: jax.Array, cand: jax.Array,
                            lanes: jax.Array, sentinel: int,
                            bm: int = 8, bk: int = 128,
                            interpret: bool = False) -> jax.Array:
    """``cand[i] ∩ adj[ids[i]]`` per row, gather fused into the probe.

    ids: int32[B] row indices, pre-clipped to ``[0, sentinel]``;
    cand: int32[B, Dc] padded sets; lanes: the padded adjacency
    int32[N+1, D] (row N all-sentinel) as :func:`lane_rows`. Returns
    int32[B, Dc] in ``cand``'s slots. ``Dc`` must be a multiple of ``bk``
    and ``B`` of ``bm`` (callers pad; see ops.fused_gather_intersect).
    Batches above :data:`SMEM_ROWS` run as several launches.
    """
    B, Dc = cand.shape
    nc = lanes.shape[0] // (sentinel + 1)         # lane rows per adj row
    assert lanes.shape == ((sentinel + 1) * nc, LANE), lanes.shape
    assert ids.shape == (B,), (ids.shape, cand.shape)
    assert Dc % bk == 0, f"Dc={Dc} not a multiple of bk={bk}"
    assert B % bm == 0, f"B={B} not a multiple of bm={bm}"
    step = SMEM_ROWS - SMEM_ROWS % bm
    if B > step:
        return jnp.concatenate([
            gather_intersect_pallas(ids[i:i + step], cand[i:i + step], lanes,
                                    sentinel, bm=bm, bk=bk,
                                    interpret=interpret)
            for i in range(0, B, step)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bm,),
        in_specs=[
            pl.BlockSpec((bm, Dc), lambda i, ids: (i, 0)),
            # the fused gather: rows are DMA'd by id inside the kernel —
            # no materialized [B, D] intermediate
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bm, Dc), lambda i, ids: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm * nc, LANE), lanes.dtype),
                        pltpu.SemaphoreType.DMA((bm,))],
    )
    return pl.pallas_call(
        functools.partial(_gather_intersect_kernel, nc=nc,
                          sentinel=sentinel, bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Dc), cand.dtype),
        interpret=interpret,
        name="gather_intersect_pallas",
    )(ids, cand, lanes)
