"""Vectorized (TPU-native) executor for BENU execution plans.

The paper's runtime is a MIMD task pool: one backtracking DFS per start
vertex. A TPU pod is a lockstep SPMD machine, so we re-express Algorithm 1's
recursion as **level-synchronous frontier expansion**: a frontier is a batch
of partial matches (one row per partial match); every instruction of the
execution plan acts on the whole frontier at once:

    INI   materialize the start-vertex column
    DBQ   gather adjacency rows for a frontier column     (the on-demand
          shuffle: local gather here; all_to_all in engine_dist)
    INT   row-wise padded-set intersection (Pallas kernel on TPU)
    TRC   semantically identical to INT under SPMD static shapes — the
          memoization win of the paper's per-task dict cache shows up as
          *DBQ dedup* (see engine_dist / unique-based fetch), not as saved
          FLOPs, because a lockstep batch always executes its full shape
    ENU   expand each row by its candidate set and compact valid children
          into a fixed-capacity child frontier (overflow is counted and the
          driver re-chunks; this is the paper's task splitting, vectorized)
    RES   count (or emit) rows that are complete matches

The DFS->BFS change preserves the *set* of matches exactly (instructions are
pure set algebra on a static schedule); only traversal order changes. Every
shape is static, so the program jits, shards, and dry-runs.

Sets are "padded-with-holes" int32 rows: entries == sentinel (= N) are
holes; valid entries ascend. Intersection keeps entries in place, so no
compaction is needed until ENU.
"""

from __future__ import annotations

import functools
from collections import Counter
from dataclasses import dataclass
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.storage import Graph, pad_rows, padded_width
from ..kernels import ops as kops
from .instructions import (DBQ, ENU, INI, INT, RES, TRC, Instr, Plan, Var)
from .pattern import Pattern

FetchFn = Callable[[jax.Array], jax.Array]   # ids int32[B] -> rows int32[B,D]


# --------------------------------------------------------------------------
# Device-resident graph
# --------------------------------------------------------------------------


#: rows packed on the host per transfer in ``DeviceGraph.from_graph``
HOST_BLOCK_ROWS = 1 << 14


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(table: jax.Array, part: jax.Array, lo) -> jax.Array:
    """``table[lo:lo + len(part)] = part``, in place (``table`` donated)."""
    return jax.lax.dynamic_update_slice(table, part, (lo, 0))


@dataclass
class DeviceGraph:
    """Padded adjacency rows on device. Row ``n`` (sentinel row) is all-holes
    so gathers with invalid ids are safe."""

    rows: jax.Array        # int32[N+1, D]
    n: int                 # number of real vertices; sentinel value

    @property
    def d(self) -> int:
        return self.rows.shape[1]

    @staticmethod
    def from_graph(graph: Graph, d_max: Optional[int] = None,
                   lane: int = 128) -> "DeviceGraph":
        """Pack and transfer the table :data:`HOST_BLOCK_ROWS` rows at a
        time, written in place on device: neither host nor device holds a
        second copy of it (2.6 GB for a 300k-vertex power-law graph). The
        sentinel row is an empty adjacency packed with the last block."""
        adj = list(graph.adj) + [np.zeros(0, np.int64)]
        d = padded_width(max(len(a) for a in adj), d_max=d_max, lane=lane)
        rows = jnp.full((len(adj), d), graph.n, jnp.int32)
        step = HOST_BLOCK_ROWS
        for lo in range(0, len(adj), step):
            part = pad_rows(adj[lo:lo + step], graph.n, d_max=d, lane=lane)
            rows = _write_rows(rows, jnp.asarray(part), lo)
        return DeviceGraph(rows=rows, n=graph.n)

    @functools.cached_property
    def lane_rows(self) -> jax.Array:
        """The rows in the fused kernel's layout (kernels/gather_intersect
        ``lane_rows``): built on first use, then kept with the graph."""
        return kops.lane_rows(self.rows, self.n)


def row_fetch(rows: jax.Array, n: int) -> FetchFn:
    """Local DBQ over ``rows`` (``int32[N+1, D]``, row ``n`` all-holes).

    Inside a jitted program ``rows`` must be one of its arguments: a
    closed-over device array is embedded in the program as a constant, a
    host copy of the whole table in every compiled chunk program.
    """

    def fetch(ids: jax.Array) -> jax.Array:
        return rows[jnp.clip(ids, 0, n)]

    return fetch


# --------------------------------------------------------------------------
# Plan preprocessing: liveness + static checks
# --------------------------------------------------------------------------


def _liveness(plan: Plan) -> List[frozenset]:
    """live[i] = vars read at instruction >= i (gathered across ENUs)."""
    live: List[frozenset] = [frozenset()] * (len(plan.instrs) + 1)
    acc: frozenset = frozenset()
    for i in range(len(plan.instrs) - 1, -1, -1):
        acc = acc | frozenset(v for v in plan.instrs[i].uses()
                              if v[0] != "op")
        live[i] = acc
    return live


def classify_fusable_dbqs(plan: Plan) -> FrozenSet[Var]:
    """DBQ targets whose gather can fuse into the intersect kernel.

    A DBQ row set is *fusable* when it is consumed exactly once, by an
    INT or TRC, as a **non-first** operand: the fused kernel
    (kernels/gather_intersect.py) then probes the running result against
    the adjacency rows directly and the ``[B, D]`` gather is never
    materialized. First operands stay materialized (their slots define
    the result layout, keeping fused runs bit-equal to unfused ones), and
    multi-use row sets stay materialized too — re-gathering per consumer
    would move more HBM bytes than the one materialization it saves
    (that reuse is exactly the paper's triangle cache). Used by both the
    engine and ``benchmarks/roofline.py --fused`` so the bytes model and
    the executed program agree.
    """
    use_count: Counter = Counter()
    for ins in plan.instrs:
        use_count.update(ins.uses())
    dbq_targets = {ins.target for ins in plan.instrs if ins.op == DBQ}
    fusable = set()
    for ins in plan.instrs:
        if ins.op == INT:
            consumed = ins.operands[1:]
        elif ins.op == TRC:
            consumed = ins.operands[3:]      # engine folds operands[2] ∩ [3]
        else:
            continue
        for v in consumed:
            if v in dbq_targets and use_count[v] == 1:
                fusable.add(v)
    return frozenset(fusable)


def check_jit_supported(plan: Plan) -> bool:
    """Validate the plan; returns True iff it consumes V(G) (detached-vertex
    matching orders, e.g. the wedge order for the square — the driver then
    additionally iterates universe chunks)."""
    n_vg = 0
    for ins in plan.instrs:
        if ins.op not in (INI, DBQ, INT, TRC, ENU, RES):
            raise NotImplementedError(
                f"engine_jax supports BENU plans only (got {ins.op}); "
                "S-BENU runs through the ref engine / engine_dist extension")
        n_vg += sum(1 for v in ins.operands if v[0] == "VG")
    if n_vg > 1:
        raise NotImplementedError(
            "plans with two detached vertices need nested universe loops; "
            "the best-plan search never emits these")
    return n_vg == 1


# --------------------------------------------------------------------------
# Instruction primitives
# --------------------------------------------------------------------------


def _apply_filters(sets: jax.Array, filters, env: Dict[Var, jax.Array],
                   sentinel: int) -> jax.Array:
    out = sets
    for op, var in filters:
        f = env[var][:, None]
        if op == "<":
            cond = out < f
        elif op == ">":
            cond = out > f
        elif op == "!=":
            cond = out != f
        else:  # pragma: no cover
            raise ValueError(op)
        out = jnp.where(cond, out, sentinel)
    return out


def _scan(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D array, blocked in 128-lane rows.

    Same integers as ``jnp.cumsum(x)``, but the TPU compiler takes seconds
    to minutes on one long 1-D cumsum (32 s at 2**20 elements on a v5e
    target) and under a second on this blocked form.
    """
    r = x.shape[0]
    if r <= 128:
        return jnp.cumsum(x)
    inner = jnp.cumsum(jnp.pad(x, (0, -r % 128)).reshape(-1, 128), axis=1)
    tot = inner[:, -1]
    return (inner + (_scan(tot) - tot)[:, None]).reshape(-1)[:r]


def _count_le(read: Callable[[jax.Array], jax.Array], x: jax.Array,
              n: int) -> jax.Array:
    """Per query ``x``, how many of the first ``n`` values of a
    non-decreasing sequence are ``<= x`` (the index of the first value
    above ``x``), capped at ``n - 1``.

    A fixed-trip binary search: ``(n - 1).bit_length()`` rounds, each one
    ``read(idx)`` of an index per query (``0 <= idx < n``).
    """
    pos = jnp.zeros_like(x)
    for k in reversed(range((n - 1).bit_length())):
        nxt = pos + (1 << k)
        ok = (nxt < n) & (read(jnp.minimum(nxt, n - 1) - 1) <= x)
        pos = jnp.where(ok, nxt, pos)
    return pos


def _expand(env: Dict[Var, jax.Array], valid: jax.Array,
            cand: jax.Array, target: Var, cap: int, live: frozenset,
            sentinel: int, compaction: str = "cumsum",
            extra_cols: Optional[Dict[Var, jax.Array]] = None
            ) -> Tuple[Dict[Var, jax.Array], jax.Array, jax.Array]:
    """ENU: frontier [B] -> child frontier [cap]. Returns (env', valid',
    overflow_count).

    ``extra_cols`` maps extra per-candidate columns (``[B, D]`` aligned with
    ``cand``) to env vars of the child frontier — the S-BENU Delta-ENU uses
    this to carry each candidate's ± snapshot selector alongside its vertex.

    Compaction of the valid children to the front, in flat row-major order:
      * "cumsum": one streaming pass takes the row-wise prefix sums of the
        valid mask and the row offsets; then each child slot ``j < cap``
        finds its candidate by two binary searches, for its parent row
        (the first whose inclusive offset exceeds ``j``) and for its column
        in that row (the first whose row prefix sum exceeds its rank).
        Cost: one pass over the B x D candidates plus cap x log2(B x D)
        reads; no candidate is scattered.
      * "sort":   stable argsort on the invalid mask — XLA lowers to a
        bitonic network, O(n log^2 n) passes over the buffer.
    Both give the same order, so results are bit-equal.
    """
    with jax.named_scope("enu"):
        B, D = cand.shape
        n = B * D
        flat = cand.reshape(n)
        valid2 = (cand != sentinel) & valid[:, None]
        if compaction == "sort":
            fvalid = valid2.reshape(n)
            order = jnp.argsort(~fvalid, stable=True)    # valid rows first
            take = order[:cap]
            new_valid = fvalid[take]
            parents = jnp.repeat(jnp.arange(B, dtype=jnp.int32), D)[take]
            total = jnp.sum(fvalid)
        else:
            inner = jnp.cumsum(valid2.astype(jnp.int32), axis=1)
            tot = inner[:, -1]
            rincl = _scan(tot)
            total = rincl[-1]
            j = jnp.arange(cap, dtype=jnp.int32)
            p = _count_le(lambda i: rincl[i], j, B)
            rank = j - (rincl[p] - tot[p])
            row = inner.reshape(n)
            c = _count_le(lambda i: row[p * D + i], rank, D)
            new_valid = j < total
            parents = jnp.where(new_valid, p, 0)
            take = jnp.where(new_valid, p * D + c, 0)
        overflow = jnp.maximum(total - jnp.sum(new_valid), 0)
        new_env: Dict[Var, jax.Array] = {}
        for v, arr in env.items():
            if v in live:
                new_env[v] = arr[parents]
        new_env[target] = jnp.where(new_valid, flat[take], sentinel)
        if extra_cols:
            for v, arr in extra_cols.items():
                new_env[v] = jnp.where(new_valid, arr.reshape(n)[take], 0)
    return new_env, new_valid, overflow


def _vcbc_row_counts(plan: Plan, env: Dict[Var, jax.Array],
                     valid: jax.Array, sentinel: int,
                     report: Sequence[Var]) -> jax.Array:
    """Exact per-row match counts for VCBC-compressed plans.

    Non-core vertices are pairwise non-adjacent (V_c is a vertex cover), so
    the plan dropped (a) pairwise injectivity and (b) symmetry order
    constraints between them; we re-impose both here. Closed forms cover
    <= 2 non-core vertices (every paper pattern's compressed plan); more
    requires expansion (ref engine).
    """
    noncore = [v for v in report if v[0] == "C"]
    if len(noncore) > 2:
        raise NotImplementedError(
            f"{len(noncore)} non-core vertices; use the ref engine or a "
            "non-VCBC plan")
    if not noncore:
        return valid.astype(_count_dtype())
    sizes = {v: jnp.sum(env[v] != sentinel, axis=1) for v in noncore}
    if len(noncore) == 1:
        cnt = sizes[noncore[0]]
        return jnp.where(valid, cnt, 0).astype(_count_dtype())
    (va, vb) = noncore
    a, b = env[va], env[vb]
    ua, ub = va[1], vb[1]
    cons = set(plan.constraints)
    pair_valid = (a[:, :, None] != sentinel) & (b[:, None, :] != sentinel)
    if (ua, ub) in cons:
        cond = a[:, :, None] < b[:, None, :]
    elif (ub, ua) in cons:
        cond = a[:, :, None] > b[:, None, :]
    else:
        cond = a[:, :, None] != b[:, None, :]
    cnt = jnp.sum(pair_valid & cond, axis=(1, 2))
    return jnp.where(valid, cnt, 0).astype(_count_dtype())


# --------------------------------------------------------------------------
# Enumerator builder
# --------------------------------------------------------------------------


#: accumulator dtype: int64 when x64 is on (recommended for production —
#: Table-1-scale graphs have >2^31 matches); int32 otherwise, with the
#: driver accumulating cross-chunk totals in Python ints (exact as long as
#: each *chunk* stays below 2^31, guaranteed by the capacity bounds).
def _count_dtype():
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


@dataclass
class EnumResult:
    count: jax.Array                     # scalar: matches in batch
    overflow: jax.Array                  # scalar: dropped children
    level_sizes: Tuple[jax.Array, ...]   # frontier occupancy after each ENU
    matches: Optional[jax.Array] = None  # int32[cap, n] (if collected)
    matches_valid: Optional[jax.Array] = None


jax.tree_util.register_dataclass(
    EnumResult,
    data_fields=["count", "overflow", "level_sizes", "matches",
                 "matches_valid"],
    meta_fields=[])


def build_enumerator(plan: Plan,
                     sentinel: int,
                     caps: Sequence[int],
                     fetch: FetchFn,
                     collect_matches: bool = False,
                     intersect_impl: str = "auto",
                     post_expand: Optional[Callable] = None,
                     compaction: str = "cumsum",
                     fused_rows: Optional[jax.Array] = None,
                     gather_intersect_impl: str = "auto"
                     ) -> Callable[..., EnumResult]:
    """Compile ``plan`` into a jittable function of (starts, starts_valid
    [, universe_chunk]).

    ``caps[i]`` is the child-frontier capacity of the i-th ENU instruction.
    The returned function reports ``overflow`` > 0 when a capacity was hit —
    callers shrink the start batch or raise caps (driver: enumerate_graph).
    Plans consuming V(G) (one detached vertex, e.g. the square's wedge
    order) additionally take ``universe_chunk: int32[W]`` — a sentinel-padded
    slice of V(G); the driver sums counts over chunks. This is the paper's
    |V(G)|/θ subtask split for non-adjacent (u_k1, u_k2), vectorized.

    ``fused_rows`` (the ``[N+1, D]`` device adjacency, row N all-sentinel,
    or its ``DeviceGraph.lane_rows``) turns on the fused fetch path: DBQ
    targets classified by :func:`classify_fusable_dbqs` stay *lazy* — the
    engine carries the frontier's id column instead of gathered rows (so
    ENU re-indexes a ``[B]`` column, not a ``[B, D]`` block) and the
    consuming INT/TRC runs ``kops.fused_gather_intersect``
    (``gather_intersect_impl`` selects the kernel;
    kernels/gather_intersect.py), which never materializes the gathered
    rows. Results are bit-equal to the unfused path.
    """
    has_universe = check_jit_supported(plan)
    live = _liveness(plan)
    n_enu = sum(1 for ins in plan.instrs if ins.op == ENU)
    if len(caps) != n_enu:
        raise ValueError(f"need {n_enu} caps, got {len(caps)}")
    if collect_matches and plan.vcbc:
        raise ValueError("cannot collect raw matches from a VCBC plan")

    isect = functools.partial(kops.intersect_padded, sentinel=sentinel,
                              impl=intersect_impl)
    fusable = (classify_fusable_dbqs(plan) if fused_rows is not None
               else frozenset())
    fused = functools.partial(kops.fused_gather_intersect, rows=fused_rows,
                              sentinel=sentinel, impl=gather_intersect_impl)

    def run(starts: jax.Array, starts_valid: jax.Array,
            universe_chunk: Optional[jax.Array] = None) -> EnumResult:
        if has_universe and universe_chunk is None:
            raise ValueError("plan consumes V(G): pass universe_chunk")
        env: Dict[Var, jax.Array] = {}
        lazy: set = set()        # fusable DBQ targets currently holding ids
        valid = starts_valid
        cdt = _count_dtype()
        count = jnp.zeros((), cdt)
        overflow = jnp.zeros((), cdt)
        level_sizes: List[jax.Array] = []
        matches = None
        matches_valid = None
        enu_i = 0
        ip = 0
        while ip < len(plan.instrs):
            ins = plan.instrs[ip]
            if ins.op == INI:
                env[ins.target] = jnp.where(valid, starts, sentinel)
            elif ins.op == DBQ:
                ids = env[ins.operands[0]]
                if ins.target in fusable:
                    # lazy: keep the id column; the consuming INT/TRC
                    # fuses the gather into the intersect kernel
                    env[ins.target] = ids
                    lazy.add(ins.target)
                else:
                    with jax.named_scope("dbq"):
                        env[ins.target] = fetch(ids)
            elif ins.op in (INT, TRC):
                opvars = (list(ins.operands[2:4]) if ins.op == TRC
                          else list(ins.operands))
                res = None
                with jax.named_scope("int"):
                    for v in opvars:
                        if v[0] == "VG":
                            B = valid.shape[0]
                            s = jnp.broadcast_to(universe_chunk[None, :],
                                                 (B, universe_chunk.shape[0]))
                            res = s if res is None else isect(res, s)
                        elif v in lazy:
                            lazy.discard(v)      # single-use by construction
                            # classify_fusable_dbqs only marks non-first
                            # operands lazy (first operands define the
                            # result slots and were materialized at their
                            # DBQ), so a running result always exists here
                            assert res is not None, v
                            res = fused(res, env[v])
                        else:
                            s = env[v]
                            res = s if res is None else isect(res, s)
                    if ins.filters:
                        res = _apply_filters(res, ins.filters, env, sentinel)
                env[ins.target] = res
            elif ins.op == ENU:
                cand = env[ins.operands[0]]
                env, valid, ov = _expand(env, valid, cand, ins.target,
                                         caps[enu_i], live[ip + 1], sentinel,
                                         compaction=compaction)
                overflow = overflow + ov.astype(cdt)
                if post_expand is not None:
                    env, valid = post_expand(env, valid)
                level_sizes.append(jnp.sum(valid))
                enu_i += 1
            elif ins.op == RES:
                if plan.vcbc:
                    count = count + jnp.sum(
                        _vcbc_row_counts(plan, env, valid, sentinel,
                                         ins.report)).astype(cdt)
                else:
                    count = count + jnp.sum(valid).astype(cdt)
                    if collect_matches:
                        cols = [env[v] for v in ins.report]
                        matches = jnp.stack(cols, axis=1)
                        matches_valid = valid
            ip += 1
        return EnumResult(count=count, overflow=overflow,
                          level_sizes=tuple(level_sizes),
                          matches=matches, matches_valid=matches_valid)

    return run


# --------------------------------------------------------------------------
# Driver: enumerate a whole graph by start-vertex chunks
# --------------------------------------------------------------------------


def default_caps(plan: Plan, batch: int, d: int,
                 growth: float = 4.0, cap_max: int = 1 << 20) -> List[int]:
    """Heuristic per-level capacities: level0 = batch * d (a start can emit
    up to deg children), then geometric growth clipped to cap_max."""
    n_enu = sum(1 for ins in plan.instrs if ins.op == ENU)
    caps = []
    cur = batch * max(d // 4, 1)
    for _ in range(n_enu):
        caps.append(int(min(max(cur, batch), cap_max)))
        cur *= growth
    return caps


def enumerate_graph(plan: Plan, graph: Graph,
                    batch: int = 256,
                    caps: Optional[Sequence[int]] = None,
                    collect_matches: bool = False,
                    intersect_impl: str = "auto",
                    universe_chunk: int = 1024,
                    max_retries: int = 6,
                    adaptive_split: bool = True) -> Dict[str, object]:
    """Run ``plan`` over every start vertex of ``graph`` on one device.

    Thin wrapper over the unified Executor API (core/executor.py): the
    shared driver re-chunks overflowing start batches (the paper's §5.2
    task splitting, vectorized) and escalates to capacity doubling only
    for single unsplittable chunks — exact in all cases.
    """
    from .executor import ExecutorConfig, JaxBackend, drive
    cfg = ExecutorConfig(batch=batch, caps=caps,
                         collect_matches=collect_matches,
                         intersect_impl=intersect_impl,
                         universe_chunk=universe_chunk,
                         max_retries=max_retries,
                         adaptive_split=adaptive_split)
    st = drive(JaxBackend(), plan, graph, cfg)
    out: Dict[str, object] = {"count": st.count,
                              "chunks_retried": st.chunks_retried
                              + st.chunks_split,
                              "chunks_split": st.chunks_split}
    if collect_matches:
        out["matches"] = st.matches
    return out
