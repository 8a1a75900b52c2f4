"""Vectorized (JIT) executor for S-BENU incremental execution plans.

``engine_jax`` re-expressed BENU's per-task backtracking as lockstep
frontier expansion; this module does the same for the streaming half of the
paper (§5): every incremental plan ΔP_i becomes a jittable function over a
batch of start vertices (the touched-vertex set of the update batch) and
the six-block device snapshot of :mod:`repro.graph.dynamic`.

What changes relative to the static engine:

    DBQ   takes a (type, direction, op) selector against the dual-snapshot
          store: ``(either, dir, +/-)`` gathers the current/previous block,
          ``unaltered`` masks previous rows lane-wise against the deleted
          delta entries, ``delta`` sign-filters the flagged delta rows.
          ``adj_op='op'`` resolves per row via the snapshot selector bound
          by the Delta-ENU (a ``where`` between the two gathers).
    DENU  Delta-ENU: expands the flagged candidate set like ENU but carries
          each child's ± flag as an extra frontier column — the per-row
          snapshot selector for every later op-dependent DBQ and for the
          ΔR_t^+ / ΔR_t^- classification at RES.
    INS   back-edge existence test: a lane-wise membership probe of the
          mapped vertex against a fetched typed row; failing rows are
          invalidated (the vectorized backtrack).

Flagged sets are value/sign row pairs: values follow the padded-set
convention (sentinel holes, ascending), signs are +1/-1 with 0 at holes.
Every shape is static, so the program jits; the unified Executor driver
(core/executor.py, ``sbenu-jax`` backend) owns chunking and overflow.

The instruction loop is split from the data source: the typed-DBQ selector
is a pluggable ``fetch(ids, type, direction, op, opsign)`` built by
:func:`make_typed_fetch` from three gather callbacks, so the same loop runs
against a resident :class:`DeviceSnapshot` (this module) or against
mesh-sharded blocks served by request/response collectives
(core/engine_sbenu_dist.py).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..graph.dynamic import DeviceSnapshot
from ..kernels import ops as kops
from .instructions import (DBQ, DENU, ENU, INI, INS, INT, RES, Instr, Plan,
                           Var)
from .engine_jax import _apply_filters, _count_dtype, _expand

#: pseudo-variable carrying the per-row snapshot selector (+1 -> G'_t,
#: -1 -> G'_{t-1}); bound by DENU, read by op-dependent DBQs and RES.
OP_VAR: Var = ("op", -1)

jax.tree_util.register_dataclass(
    DeviceSnapshot,
    data_fields=["prev_out", "prev_in", "cur_out", "cur_in",
                 "delta_out", "delta_out_sign", "delta_in", "delta_in_sign"],
    meta_fields=["n"])


def device_put_snapshot(snap: DeviceSnapshot) -> DeviceSnapshot:
    """Move the six blocks to device once per time step (the jitted runner
    then sees committed device arrays instead of re-transferring numpy)."""
    return jax.tree.map(jnp.asarray, snap)


# --------------------------------------------------------------------------
# Plan preprocessing
# --------------------------------------------------------------------------


def check_sbenu_jit_supported(plan: Plan) -> None:
    """Validate that ``plan`` is a connected-order incremental plan."""
    n_denu = 0
    for ins in plan.instrs:
        if ins.op not in (INI, DBQ, INT, ENU, DENU, INS, RES):
            raise NotImplementedError(
                f"engine_sbenu_jax cannot execute {ins.op}")
        if any(v[0] == "VG" for v in ins.operands):
            raise NotImplementedError(
                "incremental plans are rooted at the delta edge and never "
                "consume V(G)")
        n_denu += ins.op == DENU
    if n_denu != 1:
        raise NotImplementedError(
            f"expected exactly one Delta-ENU, got {n_denu}")


def _sbenu_liveness(plan: Plan) -> List[frozenset]:
    """live[i] = vars read at instruction >= i. Unlike the static engine,
    the op pseudo-variable is tracked: RES classifies matches by it."""
    live: List[frozenset] = [frozenset()] * (len(plan.instrs) + 1)
    acc: frozenset = frozenset({OP_VAR})   # RES (last instr) reads it
    for i in range(len(plan.instrs) - 1, -1, -1):
        acc = acc | frozenset(plan.instrs[i].uses())
        live[i] = acc
    return live


def plan_level_count(plan: Plan) -> int:
    """Expansion levels = DENU + ENU instructions (one capacity each)."""
    return sum(1 for ins in plan.instrs if ins.op in (ENU, DENU))


def sbenu_default_caps(plan: Plan, batch: int, d_delta: int = 0,
                       d: int = 0, growth: float = 2.0,
                       cap_max: int = 1 << 20) -> List[int]:
    """Per-level capacities for delta frontiers.

    Unlike the static engine (whose frontiers *fan out* by a degree factor
    per level), delta frontiers stay near the start-batch size: a start
    emits its handful of delta edges, and every later level intersects
    typed adjacency — almost always a contraction. Capacities therefore
    start at ``2 * batch`` and grow gently; the rare heavy step overflows
    and is re-chunked (or capacity-doubled) by the adaptive driver, which
    is far cheaper than paying a worst-case ``batch * d_delta * d`` pad on
    every chunk. ``d_delta``/``d`` only tighten the first level when the
    delta rows are known to be narrow."""
    caps: List[int] = []
    first = 2 * batch
    if d_delta:
        first = min(first, batch * max(d_delta, 1))
    cur = float(max(first, 8))
    for ins in plan.instrs:
        if ins.op in (DENU, ENU):
            caps.append(int(min(max(int(cur), batch), cap_max)))
            cur *= growth
    return caps


def sbenu_level_fanouts(plan: Plan) -> List[bool]:
    """Per expansion level: does it *fan out* (True) or contract (False)?

    A level whose candidate set is built from a single typed adjacency
    (e.g. the 4-cycle's ``C3 := Intersect(AUO2) | >f1``) multiplies the
    frontier by ~avg degree; a level intersecting >= 2 adjacencies almost
    always contracts. The DENU level is always reported as contracting —
    its exact bound (the chunk's delta-edge total) is computed separately.
    """
    from .instructions import SB_ADJ_KINDS
    defs: Dict[Var, Instr] = {}
    for ins in plan.instrs:
        if ins.target is not None:
            defs[ins.target] = ins

    def adj_inputs(var: Var, seen: frozenset) -> set:
        ins = defs.get(var)
        if ins is None or var in seen:
            return set()
        out: set = set()
        for v in ins.operands:
            if v[0] in SB_ADJ_KINDS:
                out.add(v)
            else:
                out |= adj_inputs(v, seen | {var})
        return out

    fan: List[bool] = []
    for ins in plan.instrs:
        if ins.op == DENU:
            fan.append(False)
        elif ins.op == ENU:
            fan.append(len(adj_inputs(ins.operands[0], frozenset())) < 2)
    return fan


def _resolve_intersect_impl(impl: str) -> str:
    """``auto`` -> Pallas on TPU, binary-search elsewhere (delta rows are
    kept ascending precisely so the O(D log D) path applies).

    A thin veneer over :func:`repro.kernels.dispatch.resolve_impl` — the
    one resolution order (explicit impl > ``REPRO_INTERSECT_IMPL`` env
    override > platform default) shared with kernels/ops.py; this module
    only swaps the CPU default from the dense probe to the binary search
    its ascending-row invariant enables (``_resort_fn`` maintains it).
    """
    from ..kernels.dispatch import resolve_impl
    resolved = resolve_impl("intersect", impl)
    env = os.environ.get("REPRO_INTERSECT_IMPL", "").strip()
    # env values "" and the literal "auto" are both non-overrides: in
    # either case resolve_impl fell through to the platform default, and
    # this engine's CPU default is the binary probe, not the dense one
    if impl == "auto" and resolved in ("ref", "chunked") \
            and env in ("", "auto"):
        return "binary"
    return resolved


def _resort_fn(binary: bool) -> Callable[[jax.Array], jax.Array]:
    """The binary-search intersect needs b-side rows fully ascending with
    tail holes; resort() restores that invariant after masking/filtering
    (identity for every other impl — they accept in-place holes)."""
    if binary:
        return lambda rows: jnp.sort(rows, axis=-1)
    return lambda rows: rows


# --------------------------------------------------------------------------
# Enumerator builder
# --------------------------------------------------------------------------


@dataclass
class SBenuEnumResult:
    count_plus: jax.Array                # scalar: ΔR_t^+ matches in batch
    count_minus: jax.Array               # scalar: ΔR_t^- matches in batch
    overflow: jax.Array                  # scalar: dropped children
    level_sizes: Tuple[jax.Array, ...]
    matches: Optional[jax.Array] = None        # int32[cap, n]
    match_ops: Optional[jax.Array] = None      # int32[cap] (+1/-1)
    matches_valid: Optional[jax.Array] = None  # bool[cap]


jax.tree_util.register_dataclass(
    SBenuEnumResult,
    data_fields=["count_plus", "count_minus", "overflow", "level_sizes",
                 "matches", "match_ops", "matches_valid"],
    meta_fields=[])

FlaggedRows = Tuple[jax.Array, jax.Array]       # (values, signs)

#: fetch(ids, type, direction, op, opsign) -> rows | (values, signs)
TypedFetch = Callable[..., Union[jax.Array, FlaggedRows]]


def make_typed_fetch(sentinel: int,
                     resort: Callable[[jax.Array], jax.Array],
                     gather_prev: Callable[[str, jax.Array], jax.Array],
                     gather_cur: Callable[[str, jax.Array], jax.Array],
                     gather_delta: Callable[[str, jax.Array], FlaggedRows],
                     gather_opsel: Optional[Callable] = None) -> TypedFetch:
    """The (type, direction, op) DBQ selector of §5.3.1 over three row
    gathers.

    ``gather_prev``/``gather_cur`` serve G'_{t-1}/G'_t rows for one
    direction; ``gather_delta`` serves the flagged delta (values, signs)
    pair. The lane-wise derivations (``unaltered`` masking, sign
    filtering, the per-row snapshot select) are shared by every engine —
    only the gathers differ (resident block indexing here, request/
    response collectives in the sharded engine). ``gather_opsel`` is an
    optional fast path for the op-dependent select (the resident engine's
    single offset gather over stacked prev/cur); without it the select is
    two gathers + a row-wise ``where``.
    """

    def fetch(ids: jax.Array, ty: str, direction: str, op,
              opsign: Optional[jax.Array]) -> Union[jax.Array, FlaggedRows]:
        if ty == "either":
            if op == "+":
                return gather_cur(direction, ids)
            if op == "-":
                return gather_prev(direction, ids)
            # per-row snapshot selector bound by the Delta-ENU
            if gather_opsel is not None:
                return gather_opsel(direction, ids, opsign)
            pv = gather_prev(direction, ids)
            cv = gather_cur(direction, ids)
            return jnp.where((opsign > 0)[:, None], cv, pv)
        if ty == "unaltered":
            # prev minus deleted: mask prev entries that appear with a
            # '-' flag in the delta row (lane-wise membership probe)
            rows = gather_prev(direction, ids)
            dvals, dsigns = gather_delta(direction, ids)
            deleted = jnp.where(dsigns < 0, dvals, sentinel)
            hit = jnp.any(rows[:, :, None] == deleted[:, None, :], axis=2)
            return resort(jnp.where(hit, sentinel, rows))
        if ty == "delta":
            dvals, dsigns = gather_delta(direction, ids)
            if op == "*":
                return dvals, dsigns
            want = (dsigns > 0) if op == "+" else (dsigns < 0) \
                if op == "-" else (dsigns * opsign[:, None] > 0)
            return resort(jnp.where(want, dvals, sentinel))
        raise ValueError(ty)

    return fetch


def build_sbenu_instr_runner(plan: Plan, sentinel: int, caps: Sequence[int],
                             collect_matches: bool = False,
                             intersect_impl: str = "auto",
                             compaction: str = "cumsum",
                             post_expand: Optional[Callable] = None
                             ) -> Callable[..., SBenuEnumResult]:
    """The incremental instruction loop over a pluggable typed fetch.

    Returns ``run_instrs(fetch, starts, starts_valid)`` where ``fetch`` is
    a :func:`make_typed_fetch` selector. ``post_expand(env, valid)`` (if
    given) runs after every DENU/ENU expansion — the sharded engine's
    frontier rebalancer hook, identical to the static engine's.
    """
    check_sbenu_jit_supported(plan)
    live = _sbenu_liveness(plan)
    n_lv = plan_level_count(plan)
    if len(caps) != n_lv:
        raise ValueError(f"need {n_lv} caps, got {len(caps)}")

    impl = _resolve_intersect_impl(intersect_impl)
    binary = impl == "binary"
    isect = functools.partial(kops.intersect_padded, sentinel=sentinel,
                              impl=impl)
    resort = _resort_fn(binary)

    def run_instrs(fetch: TypedFetch, starts: jax.Array,
                   starts_valid: jax.Array) -> SBenuEnumResult:
        env: Dict[Var, object] = {}
        valid = starts_valid
        cdt = _count_dtype()
        count_plus = jnp.zeros((), cdt)
        count_minus = jnp.zeros((), cdt)
        overflow = jnp.zeros((), cdt)
        level_sizes: List[jax.Array] = []
        matches = match_ops = matches_valid = None
        lv = 0
        for ip, ins in enumerate(plan.instrs):
            if ins.op == INI:
                env[ins.target] = jnp.where(valid, starts, sentinel)
            elif ins.op == DBQ:
                ids = env[ins.operands[0]]
                with jax.named_scope("dbq"):
                    env[ins.target] = fetch(ids, ins.adj_type, ins.adj_dir,
                                            ins.adj_op, env.get(OP_VAR))
            elif ins.op == INT:
                sets = [env[v] for v in ins.operands]
                flagged = [s for s in sets if isinstance(s, tuple)]
                plain = [s for s in sets if not isinstance(s, tuple)]
                with jax.named_scope("int"):
                    if flagged:
                        # the delta candidate set: flag-aware filtering
                        # keeps values and signs aligned (Delta-ENU
                        # consumes both)
                        assert len(flagged) == 1
                        vals, signs = flagged[0]
                        for other in plain:
                            vals = isect(vals, other)
                        if ins.filters:
                            vals = _apply_filters(vals, ins.filters, env,
                                                  sentinel)
                        signs = jnp.where(vals != sentinel, signs, 0)
                        env[ins.target] = (vals, signs)
                    else:
                        res = plain[0]
                        for other in plain[1:]:
                            res = isect(res, other)
                        if ins.filters:
                            res = _apply_filters(res, ins.filters, env,
                                                 sentinel)
                        env[ins.target] = resort(res)
            elif ins.op in (ENU, DENU):
                extra = None
                if ins.op == DENU:
                    cand, signs = env[ins.operands[0]]
                    extra = {OP_VAR: signs}
                else:
                    cand = env[ins.operands[0]]
                plain_env = {v: a for v, a in env.items()
                             if not isinstance(a, tuple)}
                plain_env, valid, ov = _expand(
                    plain_env, valid, cand, ins.target, caps[lv],
                    live[ip + 1], sentinel, compaction=compaction,
                    extra_cols=extra)
                env = plain_env
                overflow = overflow + ov.astype(cdt)
                if post_expand is not None:
                    env, valid = post_expand(env, valid)
                level_sizes.append(jnp.sum(valid))
                lv += 1
            elif ins.op == INS:
                fv = env[ins.operands[0]]
                rows = env[ins.operands[1]]
                with jax.named_scope("int"):
                    hit = jnp.any(rows == fv[:, None], axis=1)
                    valid = valid & hit & (fv != sentinel)
            elif ins.op == RES:
                opsign = env[OP_VAR]
                count_plus = count_plus + jnp.sum(
                    valid & (opsign > 0)).astype(cdt)
                count_minus = count_minus + jnp.sum(
                    valid & (opsign < 0)).astype(cdt)
                if collect_matches:
                    matches = jnp.stack([env[v] for v in ins.report], axis=1)
                    match_ops = opsign
                    matches_valid = valid
        return SBenuEnumResult(count_plus=count_plus,
                               count_minus=count_minus,
                               overflow=overflow,
                               level_sizes=tuple(level_sizes),
                               matches=matches, match_ops=match_ops,
                               matches_valid=matches_valid)

    return run_instrs


def build_sbenu_enumerator(plan: Plan, sentinel: int, caps: Sequence[int],
                           collect_matches: bool = False,
                           intersect_impl: str = "auto",
                           compaction: str = "cumsum"
                           ) -> Callable[..., SBenuEnumResult]:
    """Compile an incremental plan into a jittable function of
    ``(snap: DeviceSnapshot, starts int32[B], starts_valid bool[B])``.

    ``caps[i]`` is the child-frontier capacity of the i-th expansion level
    (DENU or ENU). Overflow reporting follows the static engine: a result
    with ``overflow > 0`` must be discarded and re-chunked by the driver.
    """
    run_instrs = build_sbenu_instr_runner(
        plan, sentinel, caps, collect_matches=collect_matches,
        intersect_impl=intersect_impl, compaction=compaction)
    resort = _resort_fn(_resolve_intersect_impl(intersect_impl) == "binary")

    def run(snap: DeviceSnapshot, starts: jax.Array,
            starts_valid: jax.Array) -> SBenuEnumResult:
        n = snap.n
        assert n == sentinel, "snapshot/plan sentinel mismatch"
        rows_total = snap.prev_out.shape[0]      # n + 1, or mesh-padded
        # prev/cur stacked per direction: the per-row snapshot selector
        # becomes a single offset gather instead of two gathers + where
        # (XLA CSEs the concats across repeated DBQs and fused plans)
        with jax.named_scope("dbq"):
            stacked = {di: jnp.concatenate([p, c], axis=0) for di, p, c in
                       (("out", snap.prev_out, snap.cur_out),
                        ("in", snap.prev_in, snap.cur_in))}
        prev = {"out": snap.prev_out, "in": snap.prev_in}
        cur = {"out": snap.cur_out, "in": snap.cur_in}
        delta = {"out": (snap.delta_out, snap.delta_out_sign),
                 "in": (snap.delta_in, snap.delta_in_sign)}

        def gather(block: jax.Array, ids: jax.Array) -> jax.Array:
            return block[jnp.clip(ids, 0, n)]

        def gather_prev(direction: str, ids: jax.Array) -> jax.Array:
            return gather(prev[direction], ids)

        def gather_cur(direction: str, ids: jax.Array) -> jax.Array:
            return gather(cur[direction], ids)

        def gather_delta(direction: str, ids: jax.Array) -> FlaggedRows:
            dvals, dsigns = delta[direction]
            return gather(dvals, ids), gather(dsigns, ids)

        def gather_opsel(direction: str, ids: jax.Array,
                         opsign: jax.Array) -> jax.Array:
            side = jnp.where(opsign > 0, rows_total, 0)
            return stacked[direction][jnp.clip(ids, 0, n) + side]

        fetch = make_typed_fetch(sentinel, resort, gather_prev, gather_cur,
                                 gather_delta, gather_opsel)
        return run_instrs(fetch, starts, starts_valid)

    return run


def build_sbenu_multi_enumerator(plans: Sequence[Plan], sentinel: int,
                                 caps_list: Sequence[Sequence[int]],
                                 collect_matches: bool = False,
                                 intersect_impl: str = "auto",
                                 compaction: str = "cumsum"
                                 ) -> Callable[..., SBenuEnumResult]:
    """Fuse every incremental plan ΔP_i into ONE jittable function.

    A time step runs all m plans over the same start chunk; dispatching
    them as one XLA program removes m-1 dispatch/sync round-trips per
    chunk and lets XLA CSE the shared snapshot gathers. Counts and
    overflow are summed; collected matches are concatenated (each plan's
    matches are disjoint by Theorem 5).
    """
    runs = [build_sbenu_enumerator(p, sentinel, c,
                                   collect_matches=collect_matches,
                                   intersect_impl=intersect_impl,
                                   compaction=compaction)
            for p, c in zip(plans, caps_list)]

    def run(snap: DeviceSnapshot, starts: jax.Array,
            starts_valid: jax.Array) -> SBenuEnumResult:
        rs = [r(snap, starts, starts_valid) for r in runs]
        matches = match_ops = matches_valid = None
        if collect_matches:
            matches = jnp.concatenate([r.matches for r in rs], axis=0)
            match_ops = jnp.concatenate([r.match_ops for r in rs], axis=0)
            matches_valid = jnp.concatenate([r.matches_valid for r in rs],
                                            axis=0)
        return SBenuEnumResult(
            count_plus=sum(r.count_plus for r in rs),
            count_minus=sum(r.count_minus for r in rs),
            overflow=sum(r.overflow for r in rs),
            level_sizes=tuple(s for r in rs for s in r.level_sizes),
            matches=matches, match_ops=match_ops,
            matches_valid=matches_valid)

    return run
