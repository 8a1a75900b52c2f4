"""DistributedRowStore: the paper's distributed KV database, TPU-native.

The paper stores adjacency sets in HBase and lets tasks query rows on
demand. On a TPU mesh the store *is* program state: padded adjacency rows
live block-partitioned over the devices of one mesh axis, and a DBQ over a
batch of vertex ids becomes a **batched request/response all_to_all**:

    1. dedup the local id batch (``jnp.unique`` with static size) — the
       vectorized analogue of the paper's per-task DB cache: within a
       frontier level each distinct row crosses the wire at most once;
    2. route ids to their owner shard (block partition => owner = id // rps)
       through ``all_to_all`` with a static per-peer capacity R;
    3. owners gather their local rows and ``all_to_all`` the responses back.

    Communication per level ∝ (#distinct cold ids) x row bytes — never
    ∝ #partial matches. This is the paper's headline claim expressed as
    collectives.

**Hot-row replication** (beyond-paper, replaces the LRU cache's inter-task
locality): vertices are relabeled by ascending degree at load time, so ids
``>= n_hot_lo`` are exactly the highest-degree vertices. Their rows are
replicated on every device and served locally, which removes both the
traffic and the *skew* (a hub vertex would hammer its owner shard — the
distributed-DB hotspot the paper's cache also exists to absorb).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.storage import Graph, pad_rows


@dataclass
class RowStoreSpec:
    """Static layout of a distributed row store."""

    n: int                 # real vertices; sentinel value
    d: int                 # padded row width
    n_shards: int
    rows_per_shard: int    # ceil((n+1) / n_shards), block partition
    hot: int = 0           # top-`hot` ids replicated everywhere
    req_cap: int = 0       # per-peer request capacity R (0 = B)

    @property
    def n_padded(self) -> int:
        return self.n_shards * self.rows_per_shard


def build_row_shards(graph: Graph, n_shards: int, hot: int = 0,
                     lane: int = 128, d_max: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray, RowStoreSpec]:
    """Materialize ``(shards [S, rps, D], hot_rows [hot, D], spec)``.

    Row ``n`` (the sentinel row, all holes) is stored like any other row, so
    gathers with invalid ids round-trip safely.
    """
    n = graph.n
    rps = -(-(n + 1) // n_shards)
    # the sentinel row and the shard padding are empty adjacencies, packed
    # in the same pass (one host copy of the table)
    empty = np.zeros(0, np.int64)
    rows = pad_rows(list(graph.adj) + [empty] * (n_shards * rps - n), n,
                    d_max=d_max, lane=lane)
    d = rows.shape[1]
    shards = rows.reshape(n_shards, rps, d)
    hot = min(hot, n)
    # relabeling is ascending-degree, so the hot set is ids [n-hot, n]
    hot_rows = rows[n - hot:n + 1] if hot > 0 else rows[n:n + 1]
    spec = RowStoreSpec(n=n, d=d, n_shards=n_shards, rows_per_shard=rps,
                        hot=hot)
    return shards, hot_rows, spec


def make_distributed_fetch(spec, axis: str, req_cap: int):
    """Build ``fetch(ids, local_shard, hot_rows) -> (rows, n_cold, drops)``
    for use *inside* shard_map over mesh axis ``axis``.

    ``req_cap`` (R) is the static per-peer request budget. ``drops`` counts
    requests beyond R (the driver treats drops > 0 like frontier overflow
    and retries with a smaller start batch / larger R).

    ``spec`` is duck-typed (``n`` / ``n_shards`` / ``rows_per_shard`` /
    ``hot``): the row width comes from ``local_shard`` at call time, so one
    fetch serves stores of any width sharing a layout — the streaming
    engine reuses it for all six snapshot blocks
    (:class:`~repro.graph.dynamic.SnapshotShardSpec`).
    """
    S = spec.n_shards
    rps = spec.rows_per_shard
    sent = spec.n
    hot_lo = spec.n - spec.hot  # ids >= hot_lo are replicated

    def fetch(ids: jax.Array, local_shard: jax.Array,
              hot_rows: jax.Array):
        B = ids.shape[0]
        is_hot = ids >= hot_lo                    # includes sentinel ids
        cold_ids = jnp.where(is_hot, sent, ids)
        # -- dedup (per-level DB-cache analogue)
        uids = jnp.unique(cold_ids, size=B, fill_value=sent)
        inv = jnp.searchsorted(uids, cold_ids).astype(jnp.int32)
        owner = jnp.clip(uids // rps, 0, S - 1).astype(jnp.int32)
        # slot of each unique id within its owner group (owners are sorted)
        first = jnp.searchsorted(owner, owner, side="left").astype(jnp.int32)
        slot = jnp.arange(B, dtype=jnp.int32) - first
        want = uids != sent
        ok = want & (slot < req_cap)
        drops = jnp.sum(want & ~ok)
        n_cold = jnp.sum(want)
        # -- build request matrix [S, R]
        reqs = jnp.full((S, req_cap), sent, jnp.int32)
        reqs = reqs.at[owner, slot].set(jnp.where(ok, uids, sent),
                                        mode="drop")
        # -- route requests to owners
        recv = jax.lax.all_to_all(reqs, axis, split_axis=0, concat_axis=0,
                                  tiled=False)          # [S, R] ids to serve
        # -- serve from the local block
        me = jax.lax.axis_index(axis)
        lid = recv - me * rps
        lval = (lid >= 0) & (lid < rps) & (recv != sent)
        lrows = local_shard[jnp.clip(lid, 0, rps - 1)]   # [S, R, D]
        lrows = jnp.where(lval[..., None], lrows, sent)
        # -- route responses back (same slots)
        resp = jax.lax.all_to_all(lrows, axis, split_axis=0, concat_axis=0,
                                  tiled=False)           # [S, R, D]
        flat = resp.reshape(S * req_cap, resp.shape[-1])
        got_u = flat[jnp.clip(owner * req_cap + slot, 0, S * req_cap - 1)]
        got_u = jnp.where(ok[:, None], got_u, sent)      # [B, D] unique rows
        out = got_u[inv]                                 # un-dedup
        # -- hot rows served locally
        hidx = jnp.clip(ids - hot_lo, 0, hot_rows.shape[0] - 1)
        out = jnp.where(is_hot[:, None], hot_rows[hidx], out)
        out = jnp.where((ids >= sent)[:, None], sent, out)
        return out, n_cold, drops

    return fetch
