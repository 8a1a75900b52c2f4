"""Distributed BENU: shard_map SPMD execution over a device mesh.

The paper's deployment (Fig. 7) is: data graph in a distributed KV store;
local search tasks fanned out over workers; tasks query the store on demand.
The TPU mapping:

    worker machine      -> mesh device (one shard of the enumeration axis)
    HBase region        -> block of DistributedRowStore rows in device HBM
    task queue          -> start-vertex range owned by the shard
    on-demand DBQ       -> batched all_to_all request/response
                           (see distributed/rowstore.py)
    LRU DB cache        -> per-level id dedup + replicated hot rows
    task splitting      -> fixed frontier capacities + overflow retries
    skew / stragglers   -> opt-in frontier **rebalancing**: after each ENU
                           the compacted child frontier is striped
                           round-robin over the axis with one all_to_all —
                           per-device load equalizes to ±S rows. The bytes
                           moved are bounded by cap x row-width, exactly the
                           paper's bounded subtask shuffle (§6.3), never
                           proportional to total matches.

All devices run the *same static instruction schedule* (lockstep SPMD), so
collectives are trivially congruent — there is no data-dependent control
flow anywhere in the compiled program.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..distributed.rowstore import RowStoreSpec, make_distributed_fetch
from ..graph.storage import Graph
from .engine_jax import build_enumerator, check_jit_supported
from .instructions import ENU, Plan


def enumeration_mesh(axis: str = "shard",
                     devices: Optional[Sequence] = None) -> Mesh:
    """Flat 1-D mesh over all (or given) devices for the enumeration axis."""
    devs = np.array(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis,))


@dataclass
class DistEnumStats:
    count: int
    per_shard_counts: np.ndarray
    per_shard_level_sizes: np.ndarray      # [levels, S]
    cold_rows_fetched: int                 # distinct rows that crossed wire
    request_drops: int
    overflow: int
    chunks_retried: int


def _rebalancer(axis: str, n_shards: int):
    """Round-robin stripe exchange: child i -> shard (i mod S)."""

    def post_expand(env: Dict, valid: jax.Array):
        cap = valid.shape[0]
        assert cap % n_shards == 0, "cap must be divisible by mesh size"
        w = cap // n_shards

        def shuf(x: jax.Array) -> jax.Array:
            # true round-robin: child i -> shard (i mod S); a compacted
            # (valid-first) frontier therefore spreads evenly
            xs = x.reshape((w, n_shards) + x.shape[1:]).swapaxes(0, 1)
            xs = jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=0,
                                    tiled=False)
            return xs.swapaxes(0, 1).reshape((cap,) + x.shape[1:])

        env2 = {k: shuf(v) for k, v in env.items()}
        return env2, shuf(valid)

    return post_expand


def build_distributed_step(plan: Plan,
                           spec: RowStoreSpec,
                           mesh: Mesh,
                           axis: str,
                           caps: Sequence[int],
                           req_cap: int,
                           rebalance: bool = False,
                           intersect_impl: str = "auto",
                           compaction: str = "cumsum"):
    """shard_map'd enumeration step.

    Returns ``step(shards, hot_rows, starts, starts_valid[, uni]) ->
    (counts[S], overflow[S], cold[S], drops[S], levels[L, S])``.

    ``shards``: int32[S, rps, D] sharded over ``axis``; ``hot_rows``
    replicated; ``starts``/``starts_valid``: [S*B] sharded. This function is
    what the multi-pod dry-run lowers for the paper's own technique.
    """
    has_universe = check_jit_supported(plan)
    S = spec.n_shards
    n_levels = sum(1 for ins in plan.instrs if ins.op == ENU)

    def local_fn(shards, hot_rows, starts, starts_valid, uni=None):
        local_shard = shards[0]            # [rps, D]
        dist_fetch = make_distributed_fetch(spec, axis, req_cap)
        fetch_stats: List[Tuple[jax.Array, jax.Array]] = []

        def fetch(ids: jax.Array) -> jax.Array:
            rows, n_cold, drops = dist_fetch(ids, local_shard, hot_rows)
            fetch_stats.append((n_cold, drops))
            return rows

        post = _rebalancer(axis, S) if rebalance else None
        run = build_enumerator(plan, spec.n, caps, fetch,
                               intersect_impl=intersect_impl,
                               post_expand=post, compaction=compaction)
        if has_universe:
            res = run(starts, starts_valid, uni)
        else:
            res = run(starts, starts_valid)
        cold = sum((c for c, _ in fetch_stats), jnp.zeros((), jnp.int32))
        drops = sum((d for _, d in fetch_stats), jnp.zeros((), jnp.int32))
        levels = (jnp.stack(res.level_sizes)[:, None]
                  if res.level_sizes else jnp.zeros((0, 1), jnp.int32))
        return (res.count[None], res.overflow[None], cold[None],
                drops[None], levels)

    in_specs = [P(axis, None, None), P(None, None), P(axis), P(axis)]
    out_specs = (P(axis), P(axis), P(axis), P(axis), P(None, axis))
    if has_universe:
        in_specs.append(P(None))
    fn = shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def enumerate_distributed(plan: Plan, graph: Graph,
                          mesh: Optional[Mesh] = None,
                          axis: str = "shard",
                          batch_per_shard: int = 64,
                          caps: Optional[Sequence[int]] = None,
                          req_cap: Optional[int] = None,
                          hot: int = 0,
                          rebalance: bool = False,
                          universe_chunk: int = 1024,
                          intersect_impl: str = "auto",
                          max_retries: int = 6,
                          adaptive_split: bool = True) -> DistEnumStats:
    """Enumerate ``plan`` over ``graph`` on every device of ``mesh``.

    Thin wrapper over the unified Executor API (core/executor.py): the
    shared adaptive driver re-chunks overflowing global batches (keeping
    shard-divisible shapes) before escalating capacities / request
    budgets — exact in all cases. ``cold_rows_fetched`` is the paper's
    "network communication cost" metric for Fig. 10-style experiments.
    """
    from .executor import DistBackend, ExecutorConfig, drive
    if mesh is None:
        mesh = enumeration_mesh(axis)
    S = mesh.devices.size
    backend = DistBackend(mesh=mesh, axis=axis, hot=hot,
                          rebalance=rebalance, req_cap=req_cap)
    cfg = ExecutorConfig(batch=S * batch_per_shard, caps=caps,
                         universe_chunk=universe_chunk,
                         intersect_impl=intersect_impl,
                         max_retries=max_retries,
                         adaptive_split=adaptive_split)
    st = drive(backend, plan, graph, cfg)
    return DistEnumStats(
        count=st.count,
        per_shard_counts=st.extras["per_shard_counts"],
        per_shard_level_sizes=st.extras["per_shard_level_sizes"],
        cold_rows_fetched=st.extras["cold_rows_fetched"],
        request_drops=st.drops_seen,
        overflow=0, chunks_retried=st.chunks_retried + st.chunks_split)
