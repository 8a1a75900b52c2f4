"""Distributed S-BENU: shard_map SPMD delta-frontier enumeration.

``engine_dist`` maps the paper's static deployment (Fig. 7) onto a device
mesh; this module does the same for the *streaming* half (§5, Alg. 4).
The six-block dual snapshot of :mod:`repro.graph.dynamic` is row-block
partitioned over the enumeration axis (owner of vertex v's rows =
``v // rows_per_shard``) exactly the way ``DistBackend`` shards static
adjacency rows, with the ``hot`` highest-id rows of every block
replicated (a hub set when the stream is degree-relabeled; see
``SnapshotShardSpec``):

    worker machine         -> mesh device (one shard of the axis)
    two-form vertex value  -> the shard's rows of all six blocks
                              (prev/cur/delta x out/in), resident across
                              time steps (graph/dynamic.py
                              ShardedDeviceSnapshotStore)
    typed on-demand DBQ    -> batched request/response all_to_all against
                              the owning shard of the addressed block —
                              the paper's distributed KV lookup; the
                              flagged delta row moves as ONE joint
                              (values ++ signs) exchange
    LRU DB cache           -> per-level id dedup + replicated hot rows
    ΔR_t^± result sets     -> per-shard counts (and optionally match
                              rows), reduced across the mesh by the
                              driver
    skew / stragglers      -> the same round-robin frontier rebalancer as
                              the static engine, applied after every
                              Delta-ENU / ENU expansion

Communication happens **only at typed-DBQ boundaries** (plus the opt-in
rebalance shuffle and the final count reduce): frontier expansion, INS
probes, and intersections are shard-local, so bytes moved scale with
distinct cold rows — never with partial matches. All devices run the same
static instruction schedule (lockstep SPMD), so the collectives are
trivially congruent.

The instruction loop itself is :func:`~repro.core.engine_sbenu_jax.
build_sbenu_instr_runner` — identical math to the single-device engine;
only the three gathers behind the typed-DBQ selector differ.
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..distributed.rowstore import make_distributed_fetch
from ..graph.dynamic import SnapshotShardSpec
from .engine_dist import _rebalancer
from .engine_sbenu_jax import (FlaggedRows, _resolve_intersect_impl,
                               _resort_fn, build_sbenu_instr_runner,
                               make_typed_fetch)
from .instructions import Plan

#: positional order of the sharded value blocks / their replicated hot
#: slices in the step signature (matches ShardedDeviceSnapshotStore
#: .step_sharded() keys)
BLOCK_ORDER = ("prev_out", "cur_out", "prev_in", "cur_in",
               "delta_joint_out", "delta_joint_in")


def build_sbenu_dist_step(plans: Sequence[Plan], sentinel: int,
                          spec: SnapshotShardSpec, mesh: Mesh, axis: str,
                          caps_list: Sequence[Sequence[int]], req_cap: int,
                          rebalance: bool = False,
                          collect_matches: bool = False,
                          intersect_impl: str = "auto",
                          compaction: str = "cumsum") -> Callable:
    """shard_map'd streaming enumeration step, all ΔP_i plans fused.

    Returns ``step(*blocks, *hot_blocks, starts, starts_valid)`` (block
    order :data:`BLOCK_ORDER`; ``starts``/``starts_valid``: ``[S*B]``
    sharded over ``axis``) producing per-shard
    ``(count_plus[S], count_minus[S], overflow[S], cold[S], drops[S],
    levels[L, S])`` plus, when ``collect_matches``, the gathered
    ``(matches [S*M, n], match_ops [S*M], matches_valid [S*M])`` where M
    sums the last-level capacities over plans.

    ``caps_list[i]`` are the *per-shard* frontier capacities of plan i;
    with ``rebalance`` they must be divisible by the mesh size (the
    driver's ``cap_multiple`` contract).
    """
    S = spec.n_shards
    post = _rebalancer(axis, S) if rebalance else None
    runners = [build_sbenu_instr_runner(p, sentinel, c,
                                        collect_matches=collect_matches,
                                        intersect_impl=intersect_impl,
                                        compaction=compaction,
                                        post_expand=post)
               for p, c in zip(plans, caps_list)]
    resort = _resort_fn(_resolve_intersect_impl(intersect_impl) == "binary")

    def local_fn(prev_out, cur_out, prev_in, cur_in, dj_out, dj_in,
                 h_prev_out, h_cur_out, h_prev_in, h_cur_in, h_dj_out,
                 h_dj_in, starts, starts_valid):
        row_fetch = make_distributed_fetch(spec, axis, req_cap)
        fetch_stats: List[Tuple[jax.Array, jax.Array]] = []

        def served(local: jax.Array, hot: jax.Array,
                   ids: jax.Array) -> jax.Array:
            rows, n_cold, drops = row_fetch(ids, local, hot)
            fetch_stats.append((n_cold, drops))
            return rows

        prev = {"out": (prev_out, h_prev_out), "in": (prev_in, h_prev_in)}
        cur = {"out": (cur_out, h_cur_out), "in": (cur_in, h_cur_in)}
        dj = {"out": (dj_out, h_dj_out), "in": (dj_in, h_dj_in)}

        def gather_prev(di: str, ids: jax.Array) -> jax.Array:
            return served(*prev[di], ids)

        def gather_cur(di: str, ids: jax.Array) -> jax.Array:
            return served(*cur[di], ids)

        def gather_delta(di: str, ids: jax.Array) -> FlaggedRows:
            joint = served(*dj[di], ids)
            dd = joint.shape[1] // 2
            vals, signs = joint[:, :dd], joint[:, dd:]
            # rows the fetch filled whole (invalid/hot-miss/dropped ids)
            # carry the sentinel in the sign half too; flag holes are 0
            return vals, jnp.where(vals == sentinel, 0, signs)

        fetch = make_typed_fetch(sentinel, resort, gather_prev, gather_cur,
                                 gather_delta)
        rs = [r(fetch, starts, starts_valid) for r in runners]
        cp = sum((r.count_plus for r in rs), jnp.zeros((), jnp.int32))
        cm = sum((r.count_minus for r in rs), jnp.zeros((), jnp.int32))
        ov = sum((r.overflow for r in rs), jnp.zeros((), jnp.int32))
        cold = sum((c for c, _ in fetch_stats), jnp.zeros((), jnp.int32))
        drops = sum((d for _, d in fetch_stats), jnp.zeros((), jnp.int32))
        levels = jnp.stack([s for r in rs for s in r.level_sizes])[:, None]
        outs = (cp[None], cm[None], ov[None], cold[None], drops[None],
                levels)
        if collect_matches:
            outs += (jnp.concatenate([r.matches for r in rs], axis=0),
                     jnp.concatenate([r.match_ops for r in rs], axis=0),
                     jnp.concatenate([r.matches_valid for r in rs], axis=0))
        return outs

    in_specs = (P(axis, None),) * 6 + (P(None, None),) * 6 \
        + (P(axis), P(axis))
    out_specs: Tuple = (P(axis),) * 5 + (P(None, axis),)
    if collect_matches:
        out_specs = out_specs + (P(axis, None), P(axis), P(axis))
    fn = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn)
