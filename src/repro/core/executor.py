"""Unified Executor API: one driver, many enumeration backends.

B-BENU's central claim is that a single backtracking execution plan can
drive very different runtimes — per-task local search (the paper's worker
model), lockstep SPMD frontier expansion (one device or a whole mesh), and
streaming delta enumeration — without ever shuffling partial results. This
module is that claim expressed as code: every engine in the repo implements
the small :class:`ExecutorBackend` protocol (its fetch / intersect / shard
specifics only) and the **same** driver owns

* plan preprocessing (universe detection, capacity defaults),
* the frontier lifecycle (start-vertex batching, universe chunking),
* overflow accounting, and
* **adaptive task splitting** (paper §5.2, vectorized): when a chunk
  reports ENU overflow the driver first *re-chunks* the offending
  start-vertex batch into smaller halves and re-descends with smaller
  frontiers (same capacities, fewer roots -> fewer children per level);
  only when a chunk can no longer be split does it escalate to capacity
  doubling. No match is ever dropped: an overflowed chunk's partial result
  is discarded and the chunk is re-executed in a shape that fits.

Backends::

    ref        pure-Python oracle interpreter        (core/ref_engine.py)
    jax        single-device vectorized frontier     (core/engine_jax.py)
    jax-gpu    same engine, fused gather+intersect
               fetch path (kernels/gather_intersect
               .py; see docs/KERNELS.md)             (core/engine_jax.py)
    dist       shard_map SPMD over a device mesh     (core/engine_dist.py)
    oocache    out-of-core: host-RAM row shards +
               bounded device cache + async prefetch (core/engine_ooc.py)
    sbenu      continuous/delta enumeration          (core/sbenu.py)
    sbenu-jax  vectorized continuous enumeration     (core/engine_sbenu_jax.py)
    sbenu-dist shard_map SPMD continuous enumeration
               over the mesh-sharded six-block
               snapshot                              (core/engine_sbenu_dist.py)

Use :func:`make_executor` (or instantiate a backend directly) and call
:meth:`Executor.run`; all engines route through here, so every launcher,
benchmark, and conformance test shares one chunk-size / overflow policy.

Example (the reference interpreter; every other engine is a drop-in
``make_executor`` name swap)::

    >>> from repro.core.executor import make_executor
    >>> from repro.core.pattern import get_pattern
    >>> from repro.core.plangen import generate_best_plan
    >>> from repro.graph.generate import erdos_renyi
    >>> g = erdos_renyi(30, 60, seed=1)                # 30 vertices
    >>> plan = generate_best_plan(get_pattern("triangle"), g.stats())
    >>> stats = make_executor("ref").run(plan, g, batch=8)
    >>> stats.count == make_executor("ref").run(plan, g, batch=32).count
    True
"""

from __future__ import annotations

import itertools
from abc import ABC, abstractmethod
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

import numpy as np

from .. import obs
from ..graph.storage import Graph
from .instructions import ENU, Plan
from .pattern import Pattern


# --------------------------------------------------------------------------
# Shared frontier-lifecycle helpers (previously copied in every engine)
# --------------------------------------------------------------------------


def ceil_div(a: int, b: int) -> int:
    """``ceil(a / b)`` for non-negative ints (no float detour)."""
    return -(-a // b)


def start_id_batches(n: int, batch: int,
                     sentinel: Optional[int] = None
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Yield ``(ids int32[batch], valid bool[batch])`` covering ``range(n)``."""
    sent = n if sentinel is None else sentinel
    for s0 in range(0, n, batch):
        ids = np.arange(s0, s0 + batch, dtype=np.int32)
        valid = ids < n
        yield np.where(valid, ids, sent).astype(np.int32), valid


def build_universe_chunks(n: int, width: int,
                          sentinel: Optional[int] = None) -> List[np.ndarray]:
    """Sentinel-padded slices of V(G) for plans with a detached vertex
    (the paper's |V(G)|/θ subtask split for non-adjacent (u_k1, u_k2))."""
    sent = n if sentinel is None else sentinel
    w = min(width, max(n, 1))
    chunks: List[np.ndarray] = []
    for u0 in range(0, n, w):
        c = np.full(w, sent, np.int32)
        hi = min(u0 + w, n)
        c[:hi - u0] = np.arange(u0, hi, dtype=np.int32)
        chunks.append(c)
    return chunks


def split_id_batch(ids: np.ndarray, valid: np.ndarray, granularity: int,
                   sentinel: int
                   ) -> Optional[List[Tuple[np.ndarray, np.ndarray]]]:
    """Split a start batch into two half-shaped batches (§5.2 task split).

    The valid ids are dealt evenly into two arrays of length
    ``ceil(B/2)`` rounded up to ``granularity`` (mesh width for the
    distributed backend). Returns ``None`` when the batch cannot shrink
    further.
    """
    B = ids.shape[0]
    # ceil(B/2) rounded up to granularity: a half always fits its
    # ceil(nv/2) valid ids — no start may ever be truncated away
    half = ceil_div(ceil_div(B, 2), granularity) * granularity if B > 1 else 0
    if half < granularity or half >= B:
        return None
    vids = ids[valid]
    out = []
    for part in (vids[0::2], vids[1::2]):
        a = np.full(half, sentinel, np.int32)
        v = np.zeros(half, bool)
        k = part.shape[0]
        a[:k] = part
        v[:k] = True
        out.append((a, v))
    return out


def plan_enu_count(plan: Plan) -> int:
    """Number of ENU instructions == number of per-level capacities a
    static-engine caps tuple must carry."""
    return sum(1 for ins in plan.instrs if ins.op == ENU)


# --------------------------------------------------------------------------
# Protocol types
# --------------------------------------------------------------------------


@dataclass
class ExecutorConfig:
    """Driver-level policy shared by every backend.

    Units: ``batch`` and ``universe_chunk`` count start vertices /
    universe ids per chunk; ``caps[i]`` counts child-frontier rows at the
    i-th ENU level; ``theta`` counts C2 candidates (the interpreter's
    task-split threshold, paper §6.3).
    """

    batch: int = 256                 # global start-vertex chunk size
    caps: Optional[Sequence[int]] = None   # per-ENU frontier capacities
    universe_chunk: int = 1024       # width of V(G) slices (detached vertex)
    max_retries: int = 6             # capacity-doubling budget per chunk
    adaptive_split: bool = True      # re-chunk before growing capacities
    collect_matches: bool = False
    intersect_impl: str = "auto"
    theta: Optional[int] = None      # interpreter task-split threshold


@dataclass
class ChunkResult:
    """One chunk execution. ``overflow``/``drops`` > 0 invalidates the
    result: the driver discards it and re-chunks or escalates."""

    count: int                       # matches found in the chunk
    overflow: int = 0                # children dropped at some ENU level
    drops: int = 0                   # fetch requests beyond req_cap (dist)
    matches: Optional[np.ndarray] = None   # int32[k, plan.n], valid rows only
    extras: Dict[str, Any] = field(default_factory=dict)


@dataclass
class ExecStats:
    """Driver result: exact totals + overflow/splitting accounting."""

    count: int = 0
    chunks_run: int = 0
    chunks_split: int = 0            # adaptive re-chunk events
    chunks_retried: int = 0          # capacity/request escalations
    drops_seen: int = 0
    matches: Optional[np.ndarray] = None
    extras: Dict[str, Any] = field(default_factory=dict)

    def merge_extras(self, other: Dict[str, Any]) -> None:
        """Accumulate a chunk's extras (values must support ``+``)."""
        for k, v in other.items():
            if k in self.extras:
                self.extras[k] = self.extras[k] + v
            else:
                self.extras[k] = v


class ExecutorBackend(ABC):
    """What an engine must provide: its fetch/intersect/shard specifics.

    The driver owns chunking, retries, and splitting; backends execute one
    fixed-shape chunk at a time and report overflow honestly.
    """

    name: str = "?"
    #: start-batch shapes must be multiples of this (mesh width for SPMD)
    granularity: int = 1
    #: frontier capacities must be multiples of this: the driver rounds
    #: every caps tuple it hands out (initial and escalated) up to it.
    #: SPMD backends set the mesh size — their rebalancer stripes a local
    #: frontier round-robin over the axis, which needs cap % S == 0
    cap_multiple: int = 1
    #: whether the driver may re-chunk this backend's batches
    splittable: bool = True

    @abstractmethod
    def prepare(self, plan: Any, source: Any, config: ExecutorConfig) -> None:
        """Plan preprocessing + device placement. Called once per run."""

    @abstractmethod
    def run_chunk(self, ids: np.ndarray, valid: np.ndarray,
                  universe_chunk: Optional[np.ndarray],
                  caps: Tuple[int, ...]) -> ChunkResult:
        """Execute one fixed-shape chunk of start vertices."""

    def start_batches(self, config: ExecutorConfig
                      ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Yield ``(ids int32[batch], valid bool[batch])`` start chunks."""
        yield from start_id_batches(self._n_starts(), config.batch)

    def universe_chunks(self, config: ExecutorConfig
                        ) -> Sequence[Optional[np.ndarray]]:
        """Sentinel-padded V(G) slices (``int32[W]``) for detached-vertex
        plans; ``[None]`` when the plan never consumes V(G)."""
        return [None]

    def initial_caps(self, config: ExecutorConfig) -> Tuple[int, ...]:
        """Per-ENU child-frontier capacities (rows) for the first attempt."""
        return ()

    def grow_caps(self, caps: Tuple[int, ...]) -> Tuple[int, ...]:
        """Escalated capacities once a chunk is unsplittable (default 2x)."""
        return tuple(int(c * 2) for c in caps)

    def escalate_requests(self) -> None:
        """Called when a chunk reported request drops (dist fetch only)."""

    def finalize(self, stats: ExecStats) -> None:
        """Attach backend-specific extras to the driver stats."""

    def _n_starts(self) -> int:
        raise NotImplementedError


# --------------------------------------------------------------------------
# The adaptive task-splitting driver
# --------------------------------------------------------------------------


#: keys of ``drive`` calls made outside any keyed span (a census task)
_drive_calls = itertools.count(1)


def drive(backend: ExecutorBackend, plan: Any, source: Any,
          config: ExecutorConfig) -> ExecStats:
    """Run ``plan`` over ``source`` on ``backend`` — exactly.

    A chunk that overflows is never silently truncated: its (partial)
    result is discarded, and the driver re-descends either on two smaller
    sub-chunks (adaptive task splitting — same capacities, smaller
    frontiers) or, once a chunk is a single unsplittable batch, with
    doubled capacities.
    """
    key = None if obs.current_key() is not None else \
        ("drive", next(_drive_calls))
    with obs.span("drive", key=key):
        return _drive(backend, plan, source, config)


def _drive(backend: ExecutorBackend, plan: Any, source: Any,
           config: ExecutorConfig) -> ExecStats:
    backend.prepare(plan, source, config)
    stats = ExecStats()
    all_matches: List[np.ndarray] = []
    # every caps tuple the driver hands out is rounded up to the backend's
    # cap_multiple (read after prepare(): SPMD backends learn their mesh
    # size there). This is what keeps user-supplied or degree-derived odd
    # capacities from tripping the rebalancer's cap % mesh-size assert.
    mult = max(int(getattr(backend, "cap_multiple", 1)), 1)

    def round_caps(caps: Sequence[int]) -> Tuple[int, ...]:
        return tuple(ceil_div(int(c), mult) * mult for c in caps)

    caps0 = round_caps(backend.initial_caps(config))
    sentinel = getattr(backend, "sentinel", 0)
    for ids, valid in backend.start_batches(config):
        for uni in backend.universe_chunks(config):
            # (ids, valid, caps, escalations) — LIFO work stack
            work: List[Tuple[np.ndarray, np.ndarray, Tuple[int, ...], int]]
            work = [(ids, valid, caps0, 0)]
            while work:
                cids, cvalid, caps, tries = work.pop()
                if not cvalid.any():
                    continue
                res = backend.run_chunk(cids, cvalid, uni, caps)
                stats.chunks_run += 1
                obs.count("chunks.run")
                ok = res.overflow == 0 and res.drops == 0
                if ok:
                    stats.count += int(res.count)
                    stats.merge_extras(res.extras)
                    if res.matches is not None:
                        all_matches.append(res.matches)
                    continue
                if res.drops > 0:
                    stats.drops_seen += int(res.drops)
                    backend.escalate_requests()
                halves = None
                if (res.overflow > 0 and config.adaptive_split
                        and backend.splittable):
                    halves = split_id_batch(cids, cvalid,
                                            backend.granularity, sentinel)
                if halves is not None:
                    stats.chunks_split += 1
                    obs.count("chunks.split")
                    for h_ids, h_valid in halves:
                        work.append((h_ids, h_valid, caps, tries))
                    continue
                if tries >= config.max_retries:
                    raise RuntimeError(
                        f"[{backend.name}] chunk overflowed after "
                        f"{tries} escalations (caps={caps})")
                stats.chunks_retried += 1
                obs.count("chunks.retried")
                new_caps = round_caps(backend.grow_caps(caps)) \
                    if res.overflow else caps
                work.append((cids, cvalid, new_caps, tries + 1))
    if config.collect_matches:
        stats.matches = (np.concatenate(all_matches, axis=0) if all_matches
                         else np.zeros((0, getattr(plan, "n", 0)), np.int32))
    backend.finalize(stats)
    return stats


class Executor:
    """Facade: ``Executor(backend).run(plan, graph, batch=..., ...)``."""

    def __init__(self, backend: ExecutorBackend):
        self.backend = backend

    def run(self, plan: Any, source: Any,
            config: Optional[ExecutorConfig] = None, **kwargs) -> ExecStats:
        """Enumerate ``plan`` over ``source`` exactly; ``kwargs`` are
        :class:`ExecutorConfig` fields (``batch=``, ``caps=``, ...)."""
        cfg = config if config is not None else ExecutorConfig(**kwargs)
        return drive(self.backend, plan, source, cfg)


# --------------------------------------------------------------------------
# Backend: reference interpreter (pure Python oracle)
# --------------------------------------------------------------------------


class RefBackend(ExecutorBackend):
    """Per-task backtracking interpreter; the correctness oracle.

    Capacities do not exist here (recursion never overflows), but the
    paper's θ task splitting does: heavy start vertices split into C2
    slices inside :meth:`run_chunk`.
    """

    name = "ref"
    splittable = True

    def __init__(self, db=None, collect: str = "count",
                 pattern: Optional[Pattern] = None):
        self._db = db
        self._collect = collect
        self._given_pattern = pattern
        self.engine = None

    def prepare(self, plan: Plan, source: Graph,
                config: ExecutorConfig) -> None:
        from .ref_engine import RefEngine
        self.plan, self.graph = plan, source
        self.sentinel = source.n
        collect = self._collect
        if config.collect_matches and collect == "count":
            collect = "matches"
        self.engine = RefEngine(plan, self._pattern(plan), source,
                                db=self._db, collect=collect)
        self._theta = config.theta

    def _pattern(self, plan: Plan) -> Pattern:
        if self._given_pattern is not None:
            return self._given_pattern
        from .pattern import get_pattern
        return get_pattern(plan.pattern_name)

    def _n_starts(self) -> int:
        return self.graph.n

    def run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        from .ref_engine import tasks_for_starts
        eng = self.engine
        tasks = tasks_for_starts(self.plan, eng.pattern, self.graph,
                                 ids[valid], theta=self._theta)
        m0 = eng.counters.matches
        k0 = len(eng.matches)
        eng.run(tasks=tasks)
        matches = None
        if eng.collect == "matches":
            matches = np.asarray(eng.matches[k0:], np.int32).reshape(
                -1, self.plan.n)
        return ChunkResult(count=eng.counters.matches - m0, matches=matches)

    def finalize(self, stats: ExecStats) -> None:
        c = self.engine.counters
        stats.extras.update(
            dbq=c.dbq, int_=c.int_, trc=c.trc, trc_hits=c.trc_hits,
            enu=c.enu, per_task_work=list(c.per_task_work),
            remote_queries=self.engine.db.remote_queries,
            total_queries=self.engine.db.total_queries)


# --------------------------------------------------------------------------
# Backend: single-device vectorized frontier engine
# --------------------------------------------------------------------------


class JaxBackend(ExecutorBackend):
    """Lockstep frontier expansion on one device (core/engine_jax.py).

    ``fused`` turns on the fused gather+intersect fetch path
    (kernels/gather_intersect.py): single-use DBQ row sets are never
    materialized — the consuming INT probes the adjacency rows straight
    out of the Pallas pipeline. Left ``None``, the ``REPRO_FUSED_FETCH``
    environment toggle decides (off by default; the ``jax-gpu`` backend
    defaults it on). ``gather_intersect_impl`` picks the fused kernel
    impl (auto | pallas | interpret | ref/chunked/binary fallbacks).
    """

    name = "jax"

    #: what REPRO_FUSED_FETCH falls back to when unset and fused=None
    #: (JaxGpuBackend flips it to True)
    _fused_default = False

    def __init__(self, compaction: str = "cumsum",
                 fused: Optional[bool] = None,
                 gather_intersect_impl: str = "auto"):
        self._compaction = compaction
        self._fused_arg = fused
        self._gi_impl = gather_intersect_impl

    def prepare(self, plan: Plan, source: Graph,
                config: ExecutorConfig) -> None:
        with obs.span("prepare"):
            self._prepare(plan, source, config)

    def _prepare(self, plan: Plan, source: Graph,
                 config: ExecutorConfig) -> None:
        import jax
        from ..kernels import dispatch
        from .engine_jax import (DeviceGraph, check_jit_supported,
                                 default_caps)
        self.plan, self.graph = plan, source
        self.dg = DeviceGraph.from_graph(source)
        self.sentinel = self.dg.n
        self.has_universe = check_jit_supported(plan)
        self._caps0 = tuple(config.caps) if config.caps is not None else \
            tuple(default_caps(plan, config.batch, self.dg.d))
        self._collect = config.collect_matches
        self._intersect = config.intersect_impl
        self.fused = (self._fused_arg if self._fused_arg is not None
                      else dispatch.fused_fetch_enabled(self._fused_default))
        self._jit = jax.jit
        self._runners: Dict[Tuple[int, Tuple[int, ...]], Callable] = {}
        self._level_acc: Optional[np.ndarray] = None

    def _n_starts(self) -> int:
        return self.graph.n

    def universe_chunks(self, config: ExecutorConfig):
        if not self.has_universe:
            return [None]
        return build_universe_chunks(self.graph.n, config.universe_chunk)

    def initial_caps(self, config: ExecutorConfig) -> Tuple[int, ...]:
        return self._caps0

    def _runner(self, B: int, caps: Tuple[int, ...]) -> Callable:
        key = (B, caps)
        if key not in self._runners:
            from .engine_jax import build_enumerator, row_fetch
            plan, sentinel = self.plan, self.sentinel
            kw = dict(collect_matches=self._collect,
                      intersect_impl=self._intersect,
                      compaction=self._compaction,
                      gather_intersect_impl=self._gi_impl)

            # the adjacency (and its lane-row view) are arguments, never
            # closed over: jit would embed them in the program as constants
            def run(rows, lanes, *args):
                return build_enumerator(plan, sentinel, caps,
                                        row_fetch(rows, sentinel),
                                        fused_rows=lanes, **kw)(*args)

            self._runners[key] = obs.build_on_first_call(self._jit(run))
        return self._runners[key]

    def run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        with obs.span("chunk"):
            return self._run_chunk(ids, valid, universe_chunk, caps)

    def _run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        import jax.numpy as jnp
        with obs.span("chunk.dispatch"):
            args = (self.dg.rows, self.dg.lane_rows if self.fused else None,
                    jnp.asarray(ids), jnp.asarray(valid))
            if universe_chunk is not None:
                args = args + (jnp.asarray(universe_chunk),)
            res = self._runner(ids.shape[0], caps)(*args)
        with obs.span("chunk.wait"):
            ov = int(res.overflow)
        with obs.span("chunk.decode"):
            matches = None
            if self._collect and ov == 0 and res.matches is not None:
                m = np.asarray(res.matches)
                matches = m[np.asarray(res.matches_valid)]
            if ov == 0 and res.level_sizes:
                # accepted chunks only: aggregate frontier occupancy per
                # ENU level (benchmarks/roofline.py --fused reads this to
                # model achieved vs lane-math bytes for the fetch paths)
                lv = np.asarray([int(s) for s in res.level_sizes], np.int64)
                self._level_acc = (lv if self._level_acc is None
                                   else self._level_acc + lv)
            return ChunkResult(count=int(res.count), overflow=ov,
                               matches=matches)

    def finalize(self, stats: ExecStats) -> None:
        stats.extras.update(
            level_sizes=(self._level_acc if self._level_acc is not None
                         else np.zeros(0, np.int64)),
            fused_fetch=self.fused)


class JaxGpuBackend(JaxBackend):
    """The accelerator fetch path: ``jax`` with fused gather+intersect on.

    BENU's hot loop — gather adjacency rows, intersect with the candidate
    set — is memory-bound; this backend keeps it in VMEM/registers
    (kernels/gather_intersect.py) instead of round-tripping a ``[B, D]``
    gather block through HBM. On a TPU the dispatch registry resolves the
    fused kernel to the compiled Pallas path (chip_smoke.py refuses to
    run otherwise); on a CPU it falls back to the unfused reference
    unless interpret mode is forced (``gather_intersect_impl="interpret"``
    or ``REPRO_GATHER_INTERSECT_IMPL=pallas-interpret``), which is how the
    conformance matrix covers it. Counts and match sets are bit-equal to
    ``jax`` either way. Fusion defaults on; ``REPRO_FUSED_FETCH=0``
    turns it off (A/B debugging) without leaving this backend.
    """

    name = "jax-gpu"
    _fused_default = True

    def __init__(self, compaction: str = "cumsum",
                 gather_intersect_impl: str = "auto"):
        super().__init__(compaction=compaction,
                         gather_intersect_impl=gather_intersect_impl)


# --------------------------------------------------------------------------
# Backend: shard_map SPMD over a device mesh
# --------------------------------------------------------------------------


class DistBackend(ExecutorBackend):
    """Mesh-wide SPMD frontier engine with the distributed row store.

    The rows are placed shard by shard (``place_row_shards``), and each
    chunk's starts are dealt round-robin over the shards (start ``i`` to
    shard ``i mod S``): a census task lists its starts by degree band, so
    contiguous blocks would hand the last shard all the heavy ones.
    """

    name = "dist"

    def __init__(self, mesh=None, axis: str = "shard", hot: int = 0,
                 rebalance: bool = False, req_cap: Optional[int] = None):
        self._mesh = mesh
        self._axis = axis
        self._hot = hot
        self._rebalance = rebalance
        self._req_cap0 = req_cap

    def prepare(self, plan: Plan, source: Graph,
                config: ExecutorConfig) -> None:
        with obs.span("prepare"):
            self._prepare(plan, source, config)

    def _prepare(self, plan: Plan, source: Graph,
                 config: ExecutorConfig) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..distributed.rowstore import place_row_shards
        from .engine_jax import check_jit_supported, default_caps
        from .engine_dist import enumeration_mesh
        self.plan, self.graph = plan, source
        mesh = self._mesh if self._mesh is not None else enumeration_mesh(
            self._axis)
        self.mesh = mesh
        self.S = mesh.devices.size
        self.granularity = self.S
        self.cap_multiple = self.S       # rebalancer stripes (driver rounds)
        self.shards, self.hot_rows, spec = place_row_shards(
            source, mesh, self._axis, hot=self._hot)
        self.spec = spec
        self.sentinel = spec.n
        self.has_universe = check_jit_supported(plan)
        batch_per_shard = max(config.batch // self.S, 1)
        caps = list(config.caps) if config.caps is not None else \
            default_caps(plan, batch_per_shard, spec.d)
        # caps divisible by S for the rebalancer stripes
        self._caps0 = tuple(-(-c // self.S) * self.S for c in caps)
        self.req_cap = self._req_cap0 if self._req_cap0 is not None else \
            max(64, 2 * batch_per_shard // self.S)
        self._intersect = config.intersect_impl
        self._uni = [
            jax.device_put(jnp.asarray(c), NamedSharding(mesh, P(None)))
            for c in build_universe_chunks(source.n, config.universe_chunk)
        ] if self.has_universe else [None]
        self._id_sharding = NamedSharding(mesh, P(self._axis))
        self._steps: Dict[Tuple[Tuple[int, ...], int], Callable] = {}
        self._per_shard = np.zeros(self.S, np.int64)
        self._level_acc: Optional[np.ndarray] = None
        self._cold = 0

    def _n_starts(self) -> int:
        return self.graph.n

    def start_batches(self, config: ExecutorConfig):
        gbatch = -(-config.batch // self.S) * self.S
        yield from start_id_batches(self.graph.n, gbatch)

    def universe_chunks(self, config: ExecutorConfig):
        return self._uni

    def initial_caps(self, config: ExecutorConfig) -> Tuple[int, ...]:
        return self._caps0

    def escalate_requests(self) -> None:
        self.req_cap *= 2
        obs.count("chunks.req_escalated")

    def _step(self, caps: Tuple[int, ...], req_cap: int) -> Callable:
        key = (caps, req_cap)
        if key not in self._steps:
            from .engine_dist import build_distributed_step
            self._steps[key] = obs.build_on_first_call(build_distributed_step(
                self.plan, self.spec, self.mesh, self._axis, caps, req_cap,
                rebalance=self._rebalance, intersect_impl=self._intersect))
        return self._steps[key]

    def deal(self, x: np.ndarray) -> np.ndarray:
        """``x`` (a chunk's starts) reordered so that shard ``s``'s block
        holds elements ``s, s + S, s + 2S, ...``."""
        return np.ascontiguousarray(x.reshape(-1, self.S).T).reshape(-1)

    def run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        with obs.span("chunk"):
            return self._run_chunk(ids, valid, universe_chunk, caps)

    def _run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        import jax
        with obs.span("chunk.dispatch"):
            args = [self.shards, self.hot_rows,
                    jax.device_put(self.deal(ids), self._id_sharding),
                    jax.device_put(self.deal(valid), self._id_sharding)]
            if universe_chunk is not None:
                args.append(universe_chunk)
            step = self._step(caps, self.req_cap)
            counts, overflow, cold, drops, levels = step(*args)
        with obs.span("chunk.wait"):
            ov = int(np.sum(np.asarray(overflow)))
            dr = int(np.sum(np.asarray(drops)))
        with obs.span("chunk.decode"):
            if dr:
                obs.count("dbq.req_drops", dr)
            if ov or dr:
                return ChunkResult(count=0, overflow=ov, drops=dr)
            counts64 = np.asarray(counts, dtype=np.int64)
            self._per_shard += counts64
            n_cold = int(np.sum(np.asarray(cold)))
            self._cold += n_cold
            obs.count("dbq.cold_rows", n_cold)
            # every shard's response buffers: [S, R] rows a fetch
            obs.count("dbq.req_slots",
                      self.S * self.S * self.req_cap * step.fetches)
            lv = np.asarray(levels)
            self._level_acc = (lv if self._level_acc is None
                               else self._level_acc + lv)
            return ChunkResult(count=int(counts64.sum()))

    def finalize(self, stats: ExecStats) -> None:
        stats.extras.update(
            per_shard_counts=self._per_shard,
            per_shard_level_sizes=(
                self._level_acc if self._level_acc is not None
                else np.zeros((0, self.S))),
            cold_rows_fetched=self._cold)


# --------------------------------------------------------------------------
# Backend: out-of-core fetch path (host-RAM shards + device row cache)
# --------------------------------------------------------------------------


class OocBackend(ExecutorBackend):
    """Out-of-core vectorized enumeration (core/engine_ooc.py, paper §6).

    The padded adjacency lives in host-RAM shards
    (:class:`~repro.graph.hoststore.HostRowStore`); device memory holds a
    bounded row cache (:class:`~repro.distributed.rowcache.DeviceRowCache`:
    ``cache_rows`` LRU slots + the top-``hot``-by-degree rows pinned).
    Every DBQ level dedups its id batch and pulls only the cold rows from
    the host — communication scales with distinct cold rows, never partial
    matches — and the next chunk's start rows are prefetched
    (double-buffered async ``device_put``) while the current chunk
    computes.

    Sizing: ``cache_rows``/``hot``/``stage_rows`` count rows (``D * 4``
    bytes each); when omitted, ``cache_rows``/``hot`` default to
    ``cache_frac`` / ``hot_frac`` of the graph's N rows and
    ``stage_rows`` to ``cache_rows // 4`` per staging buffer. Worst-case
    device residency is ``cache_rows + 2 * stage_rows + hot + 1`` rows
    total (slab + both prefetch buffers + pinned hot + sentinel),
    independent of graph size.
    """

    name = "oocache"
    splittable = True

    def __init__(self, cache_rows: Optional[int] = None,
                 cache_frac: float = 0.15,
                 hot: Optional[int] = None, hot_frac: float = 0.05,
                 prefetch: bool = True, stage_rows: Optional[int] = None,
                 rows_per_shard: int = 4096,
                 compaction: str = "cumsum"):
        self._cache_rows = cache_rows
        self._cache_frac = cache_frac
        self._hot = hot
        self._hot_frac = hot_frac
        self._prefetch = prefetch
        self._stage_rows = stage_rows
        self._rows_per_shard = rows_per_shard
        self._compaction = compaction
        self.cache = None
        self.store = None

    def prepare(self, plan: Plan, source: Graph,
                config: ExecutorConfig) -> None:
        from ..distributed.rowcache import DeviceRowCache
        from ..graph.hoststore import HostRowStore
        from .engine_jax import check_jit_supported, default_caps
        from .engine_ooc import OocEngine
        self.plan, self.graph = plan, source
        n = source.n
        self.sentinel = n
        self.store = HostRowStore.from_graph(
            source, rows_per_shard=self._rows_per_shard)
        cap = self._cache_rows if self._cache_rows is not None else \
            max(1, int(n * self._cache_frac))
        hot = self._hot if self._hot is not None else \
            max(0, int(n * self._hot_frac))
        self.cache = DeviceRowCache(self.store, cap, hot=hot,
                                    stage_rows=self._stage_rows)
        self.has_universe = check_jit_supported(plan)
        self._caps0 = tuple(config.caps) if config.caps is not None else \
            tuple(default_caps(plan, config.batch, self.store.d))
        self.engine = OocEngine(plan, self.cache,
                                collect_matches=config.collect_matches,
                                intersect_impl=config.intersect_impl,
                                compaction=self._compaction)

    def _n_starts(self) -> int:
        return self.graph.n

    def start_batches(self, config: ExecutorConfig):
        """Yield start batches, prefetching batch ``k + 1``'s rows right
        before handing batch ``k`` to the driver: the async H2D copy
        overlaps batch ``k``'s segment compute (double buffering)."""
        batches = list(start_id_batches(self.graph.n, config.batch))
        for k, (ids, valid) in enumerate(batches):
            if self._prefetch and k + 1 < len(batches):
                nxt_ids, nxt_valid = batches[k + 1]
                self.cache.prefetch(nxt_ids[nxt_valid])
            yield ids, valid

    def universe_chunks(self, config: ExecutorConfig):
        if not self.has_universe:
            return [None]
        return build_universe_chunks(self.graph.n, config.universe_chunk)

    def initial_caps(self, config: ExecutorConfig) -> Tuple[int, ...]:
        return self._caps0

    def run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        count, overflow, matches, _ = self.engine.run_chunk(
            ids, valid, universe_chunk, caps)
        return ChunkResult(count=count, overflow=overflow, matches=matches)

    def finalize(self, stats: ExecStats) -> None:
        stats.extras.update(
            cache=self.cache.stats.as_dict(),
            cache_capacity_rows=self.cache.capacity_rows,
            cache_hot_rows=self.cache.hot,
            device_resident_rows=self.cache.device_rows,
            device_resident_bytes=self.cache.device_bytes,
            host_store_bytes=self.store.nbytes,
            host_store_shards=len(self.store.shards))


# --------------------------------------------------------------------------
# Backend: S-BENU continuous enumeration (delta tasks on a SnapshotStore)
# --------------------------------------------------------------------------


class SBenuBackend(ExecutorBackend):
    """Delta enumeration over a SnapshotStore (core/sbenu.py).

    Start vertices are the batch's update endpoints; heavy tasks θ-split on
    their delta adjacency list. Source = a begun SnapshotStore; plan = the
    list of incremental plans for every ΔP_i.
    """

    name = "sbenu"
    splittable = True

    def __init__(self, pattern: Pattern, cache_capacity: Optional[int] = None,
                 collect: str = "matches"):
        self._pattern = pattern
        self._cache_capacity = cache_capacity
        self._collect = collect
        self.engine = None

    def prepare(self, plans: Sequence[Plan], source,
                config: ExecutorConfig) -> None:
        from .sbenu import SBenuRefEngine
        self.store = source
        self.sentinel = -1
        self._starts = np.asarray(sorted(source.start_vertices()), np.int32)
        self.engine = SBenuRefEngine(plans, self._pattern, source,
                                     collect=self._collect,
                                     cache_capacity=self._cache_capacity)
        self._theta = config.theta

    def start_batches(self, config: ExecutorConfig):
        n = self._starts.shape[0]
        for s0 in range(0, max(n, 1), config.batch):
            ids = self._starts[s0:s0 + config.batch]
            if ids.shape[0] == 0:
                return
            yield ids, np.ones(ids.shape[0], bool)

    def run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        eng = self.engine
        c0 = eng.counters.matches_plus + eng.counters.matches_minus
        eng.run_starts(ids[valid], theta=self._theta)
        c1 = eng.counters.matches_plus + eng.counters.matches_minus
        return ChunkResult(count=c1 - c0)

    def finalize(self, stats: ExecStats) -> None:
        stats.extras.update(
            delta_plus=set(self.engine.delta_plus),
            delta_minus=set(self.engine.delta_minus),
            counters=self.engine.counters)


# --------------------------------------------------------------------------
# Backend: vectorized S-BENU (JIT delta-frontier engine over the six-block
# device snapshot)
# --------------------------------------------------------------------------


class SBenuJaxBackend(ExecutorBackend):
    """Lockstep delta-frontier enumeration (core/engine_sbenu_jax.py).

    ``plan`` is the list of incremental plans (one per ΔP_i); ``source`` is
    a *begun* SnapshotStore. Start batches cover the touched-vertex set of
    the update batch (vertices with non-empty ΔΓ_out), never all of V(G);
    every plan runs over each chunk, and a chunk whose total overflow is
    non-zero is discarded whole and re-split by the shared driver.
    """

    name = "sbenu-jax"
    splittable = True

    def __init__(self, pattern: Optional[Pattern] = None,
                 collect: str = "matches", lane: int = 8,
                 d_min: int = 0, delta_d_min: int = 0,
                 compaction: str = "cumsum",
                 snapshot_storage: str = "device"):
        self._pattern = pattern          # unused; parity with SBenuBackend
        self._collect_mode = collect
        self._lane = lane
        self._d_min = d_min
        self._delta_d_min = delta_d_min
        self._compaction = compaction
        # 'device' keeps prev blocks resident in HBM across steps;
        # 'host' keeps them in HostRowStore shards (host RAM), advanced
        # in place — zero persistent device residency between steps
        self._snapshot_storage = snapshot_storage
        # runner cache outlives prepare(): a backend reused across time
        # steps (run_timestep(backend=...)) compiles once per stream as
        # long as the snapshot widths stay pinned (d_min / delta_d_min)
        self._runners: Dict[Tuple[int, int, Tuple[int, ...]], Callable] = {}

    def prepare(self, plans: Sequence[Plan], source,
                config: ExecutorConfig) -> None:
        with obs.span("prepare"):
            self._prepare(plans, source, config)

    def _prepare(self, plans: Sequence[Plan], source,
                 config: ExecutorConfig) -> None:
        import jax
        from ..graph.dynamic import DeviceSnapshotStore
        from .engine_sbenu_jax import plan_level_count
        self.plans = list(plans)
        # the runner cache keys on plan identity: a *different* plan list
        # invalidates it (ids of collected plans could be recycled);
        # self.plans keeps the current ones alive for the cache lifetime
        plan_ids = tuple(id(p) for p in self.plans)
        if getattr(self, "_cached_plan_ids", None) != plan_ids:
            self._runners.clear()
            self._cached_plan_ids = plan_ids
        self.store = source
        self.sentinel = source.n
        self._starts = np.asarray(sorted(source.start_vertices()), np.int32)
        # device-resident dual-snapshot store: prev blocks stay on device
        # across steps; G'_t is derived lane-wise from prev + delta
        dstore = DeviceSnapshotStore.for_store(
            source, lane=self._lane, d_min=self._d_min,
            delta_d_min=self._delta_d_min,
            storage=self._snapshot_storage)
        self.snap = dstore.step_snapshot()
        # the Delta-ENU level has an exact bound: the worst chunk's total
        # delta-edge count (each start emits exactly its delta row) — far
        # tighter than batch * d_delta, keeping frontiers cache-resident
        degs = np.array([len(source.delta_adj_out(int(v)))
                         for v in self._starts], np.int64)
        B = config.batch
        denu_cap = int(max((degs[s0:s0 + B].sum()
                            for s0 in range(0, len(degs), B)), default=B))
        denu_cap = max(denu_cap, B, 8)
        # round up to a power of two: steps with similar churn share one
        # compiled shape instead of retracing every step
        denu_cap = 1 << (denu_cap - 1).bit_length()
        # average degree drives fan-out levels (single-adjacency ENUs)
        avg_deg = max(1, round(source.prev.m / max(source.n, 1)))
        # one caps tuple for the whole chunk: per-plan slices, concatenated
        # (plans have different level counts; the driver grows all slices)
        from .engine_sbenu_jax import sbenu_level_fanouts
        self._offsets: List[Tuple[int, int]] = []
        caps: List[int] = []
        for plan in self.plans:
            n_lv = plan_level_count(plan)
            if config.caps is not None:
                c = list(config.caps)[:n_lv]
                c += [c[-1]] * (n_lv - len(c))
            else:
                # contraction levels keep the exact Delta-ENU bound; a
                # fan-out level (candidates = one typed adjacency) scales
                # by ~avg degree. The driver re-splits the heavy tail.
                c, cur = [], denu_cap
                for fans in sbenu_level_fanouts(plan):
                    if fans:
                        cur = min(cur * 2 * avg_deg, 1 << 22)
                        cur = 1 << (cur - 1).bit_length()
                    c.append(cur)
            self._offsets.append((len(caps), len(caps) + len(c)))
            caps.extend(c)
        self._caps0 = tuple(caps)
        self._collect = config.collect_matches or \
            self._collect_mode == "matches"
        self._intersect = config.intersect_impl
        self._jit = jax.jit
        self._plus: List[Tuple[int, ...]] = []
        self._minus: List[Tuple[int, ...]] = []
        self._count_plus = 0
        self._count_minus = 0

    def _n_starts(self) -> int:
        return self._starts.shape[0]

    def start_batches(self, config: ExecutorConfig):
        n, B = self._starts.shape[0], config.batch
        for s0 in range(0, n, B):
            chunk = self._starts[s0:s0 + B]
            ids = np.full(B, self.sentinel, np.int32)
            ids[:chunk.shape[0]] = chunk
            valid = np.zeros(B, bool)
            valid[:chunk.shape[0]] = True
            yield ids, valid

    def initial_caps(self, config: ExecutorConfig) -> Tuple[int, ...]:
        return self._caps0

    def _runner(self, B: int, caps: Tuple[int, ...]) -> Callable:
        key = (tuple(id(p) for p in self.plans), B, caps)
        if key not in self._runners:
            from .engine_sbenu_jax import build_sbenu_multi_enumerator
            caps_list = [tuple(caps[lo:hi]) for lo, hi in self._offsets]
            run = build_sbenu_multi_enumerator(
                self.plans, self.sentinel, caps_list,
                collect_matches=self._collect,
                intersect_impl=self._intersect,
                compaction=self._compaction)
            self._runners[key] = obs.build_on_first_call(self._jit(run))
        return self._runners[key]

    def run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        with obs.span("chunk"):
            return self._run_chunk(ids, valid, caps)

    def _run_chunk(self, ids, valid, caps) -> ChunkResult:
        import jax.numpy as jnp
        with obs.span("chunk.dispatch"):
            jids, jvalid = jnp.asarray(ids), jnp.asarray(valid)
            # all ΔP_i plans run in one fused dispatch per chunk
            res = self._runner(ids.shape[0], tuple(caps))(self.snap, jids,
                                                          jvalid)
        with obs.span("chunk.wait"):
            ov = int(res.overflow)
        if ov:
            # discard the whole chunk; the driver re-splits or grows
            return ChunkResult(count=0, overflow=ov)
        with obs.span("chunk.decode"):
            cp, cm = int(res.count_plus), int(res.count_minus)
            if self._collect and res.matches is not None:
                mv = np.asarray(res.matches_valid)
                rows = np.asarray(res.matches)[mv]
                ops = np.asarray(res.match_ops)[mv]
                for row, o in zip(rows, ops):
                    (self._plus if o > 0 else self._minus).append(
                        tuple(int(x) for x in row))
            self._count_plus += cp
            self._count_minus += cm
            return ChunkResult(count=cp + cm)

    def finalize(self, stats: ExecStats) -> None:
        from .sbenu import SBenuCounters
        ctr = SBenuCounters(matches_plus=self._count_plus,
                            matches_minus=self._count_minus)
        stats.extras.update(delta_plus=set(self._plus),
                            delta_minus=set(self._minus),
                            counters=ctr)


# --------------------------------------------------------------------------
# Backend: distributed S-BENU (shard_map SPMD over the sharded six-block
# snapshot)
# --------------------------------------------------------------------------


class SBenuDistBackend(ExecutorBackend):
    """Mesh-wide SPMD delta-frontier engine (core/engine_sbenu_dist.py).

    The six-block snapshot is row-block partitioned over the enumeration
    mesh and stays resident across time steps
    (:class:`~repro.graph.dynamic.ShardedDeviceSnapshotStore`); typed DBQs
    are request/response all_to_alls against the owning shard with the
    top-``hot`` rows replicated; ΔR_t^± counts (and collected match rows)
    come back per shard and are reduced here. Start batches shard evenly
    (``granularity = S``) and frontier capacities are per *shard*, kept
    divisible by the mesh size through the driver's ``cap_multiple``
    contract (required by the opt-in rebalancer's stripe exchange).
    """

    name = "sbenu-dist"
    splittable = True

    def __init__(self, pattern: Optional[Pattern] = None,
                 collect: str = "matches", lane: int = 8,
                 d_min: int = 0, delta_d_min: int = 0,
                 compaction: str = "cumsum",
                 mesh=None, axis: str = "shard", hot: int = 0,
                 rebalance: bool = False, req_cap: Optional[int] = None):
        self._pattern = pattern          # unused; parity with SBenuBackend
        self._collect_mode = collect
        self._lane = lane
        self._d_min = d_min
        self._delta_d_min = delta_d_min
        self._compaction = compaction
        self._mesh = mesh
        self._axis = axis
        self._hot = hot
        self._rebalance = rebalance
        self._req_cap0 = req_cap
        # compiled shard_map steps outlive prepare(): one compile per
        # stream as long as snapshot widths stay pinned (d_min/delta_d_min)
        self._runners: Dict[Tuple, Callable] = {}

    def prepare(self, plans: Sequence[Plan], source,
                config: ExecutorConfig) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..graph.dynamic import ShardedDeviceSnapshotStore
        from .engine_dist import enumeration_mesh
        from .engine_sbenu_jax import plan_level_count, sbenu_level_fanouts
        self.plans = list(plans)
        plan_ids = tuple(id(p) for p in self.plans)
        if getattr(self, "_cached_plan_ids", None) != plan_ids:
            self._runners.clear()
            self._cached_plan_ids = plan_ids
        mesh = self._mesh if self._mesh is not None else enumeration_mesh(
            self._axis)
        self.mesh = mesh
        self.S = int(mesh.devices.size)
        self.granularity = self.S
        self.cap_multiple = self.S
        self.store = source
        self.sentinel = source.n
        self._starts = np.asarray(sorted(source.start_vertices()), np.int32)
        dstore = ShardedDeviceSnapshotStore.for_store(
            source, mesh, axis=self._axis, lane=self._lane,
            d_min=self._d_min, delta_d_min=self._delta_d_min,
            hot=self._hot)
        self.dstore = dstore
        blocks, hot_blocks, self.spec = dstore.step_sharded()
        from .engine_sbenu_dist import BLOCK_ORDER
        self._block_args = tuple(blocks[k] for k in BLOCK_ORDER) + \
            tuple(hot_blocks[k] for k in BLOCK_ORDER)
        self._widths = tuple(int(blocks[k].shape[1]) for k in BLOCK_ORDER)
        # global batch: a multiple of S so shard_map splits starts evenly
        self._B = ceil_div(max(config.batch, self.S), self.S) * self.S
        w = self._B // self.S
        # per-shard Delta-ENU bound: each start emits exactly its delta
        # row, and a shard owns a contiguous w-slice of the chunk — the
        # worst slice's delta-edge total bounds the local first level
        degs = np.array([len(source.delta_adj_out(int(v)))
                         for v in self._starts], np.int64)
        denu_cap = w
        for s0 in range(0, len(degs), self._B):
            chunk = degs[s0:s0 + self._B]
            for k in range(self.S):
                denu_cap = max(denu_cap, int(chunk[k * w:(k + 1) * w].sum()))
        denu_cap = max(denu_cap, 8)
        denu_cap = 1 << (denu_cap - 1).bit_length()
        avg_deg = max(1, round(source.prev.m / max(source.n, 1)))
        # one caps tuple for the whole chunk: per-plan slices, concatenated
        # (same policy as the single-device backend; driver rounds each
        # entry up to cap_multiple = S)
        self._offsets: List[Tuple[int, int]] = []
        caps: List[int] = []
        for plan in self.plans:
            n_lv = plan_level_count(plan)
            if config.caps is not None:
                c = list(config.caps)[:n_lv]
                c += [c[-1]] * (n_lv - len(c))
            else:
                c, cur = [], denu_cap
                for fans in sbenu_level_fanouts(plan):
                    if fans:
                        cur = min(cur * 2 * avg_deg, 1 << 22)
                        cur = 1 << (cur - 1).bit_length()
                    c.append(cur)
            self._offsets.append((len(caps), len(caps) + len(c)))
            caps.extend(c)
        self._caps0 = tuple(caps)
        # per-peer request budget: ~2x the worst per-owner distinct-id load
        # of a frontier level, bounded so the [S, R, D] exchange buffers
        # stay modest — a heavy level that still drops escalates (2x) and
        # the chunk retries, which is exact
        self.req_cap = self._req_cap0 if self._req_cap0 is not None else \
            max(64, min(2 * max(self._caps0) // self.S, 8192))
        self._collect = config.collect_matches or \
            self._collect_mode == "matches"
        self._intersect = config.intersect_impl
        self._id_sharding = NamedSharding(mesh, P(self._axis))
        self._plus: List[Tuple[int, ...]] = []
        self._minus: List[Tuple[int, ...]] = []
        self._count_plus = 0
        self._count_minus = 0
        self._per_shard = np.zeros(self.S, np.int64)
        self._level_acc: Optional[np.ndarray] = None
        self._cold = 0

    def _n_starts(self) -> int:
        return self._starts.shape[0]

    def start_batches(self, config: ExecutorConfig):
        n, B = self._starts.shape[0], self._B
        for s0 in range(0, n, B):
            chunk = self._starts[s0:s0 + B]
            ids = np.full(B, self.sentinel, np.int32)
            ids[:chunk.shape[0]] = chunk
            valid = np.zeros(B, bool)
            valid[:chunk.shape[0]] = True
            yield ids, valid

    def initial_caps(self, config: ExecutorConfig) -> Tuple[int, ...]:
        return self._caps0

    def escalate_requests(self) -> None:
        self.req_cap *= 2

    def _runner(self, caps: Tuple[int, ...]) -> Callable:
        key = (self._cached_plan_ids, caps, self.req_cap, self._widths)
        if key not in self._runners:
            from .engine_sbenu_dist import build_sbenu_dist_step
            caps_list = [tuple(caps[lo:hi]) for lo, hi in self._offsets]
            self._runners[key] = build_sbenu_dist_step(
                self.plans, self.sentinel, self.spec, self.mesh,
                self._axis, caps_list, self.req_cap,
                rebalance=self._rebalance, collect_matches=self._collect,
                intersect_impl=self._intersect,
                compaction=self._compaction)
        return self._runners[key]

    def run_chunk(self, ids, valid, universe_chunk, caps) -> ChunkResult:
        import jax
        import jax.numpy as jnp
        jids = jax.device_put(jnp.asarray(ids), self._id_sharding)
        jvalid = jax.device_put(jnp.asarray(valid), self._id_sharding)
        out = self._runner(tuple(caps))(*self._block_args, jids, jvalid)
        cp, cm, ov, cold, drops, levels = out[:6]
        ov = int(np.sum(np.asarray(ov)))
        dr = int(np.sum(np.asarray(drops)))
        if ov or dr:
            # discard the whole mesh-wide chunk; the driver re-splits
            # (granularity S) or escalates caps / request budgets
            return ChunkResult(count=0, overflow=ov, drops=dr)
        cps = np.asarray(cp, np.int64)
        cms = np.asarray(cm, np.int64)
        self._per_shard += cps + cms
        self._cold += int(np.sum(np.asarray(cold)))
        lv = np.asarray(levels)
        self._level_acc = (lv if self._level_acc is None
                           else self._level_acc + lv)
        if self._collect:
            m, mo, mv = out[6:]
            mv = np.asarray(mv)
            rows = np.asarray(m)[mv]
            ops = np.asarray(mo)[mv]
            for row, o in zip(rows, ops):
                (self._plus if o > 0 else self._minus).append(
                    tuple(int(x) for x in row))
        self._count_plus += int(cps.sum())
        self._count_minus += int(cms.sum())
        return ChunkResult(count=int(cps.sum() + cms.sum()))

    def finalize(self, stats: ExecStats) -> None:
        from .sbenu import SBenuCounters
        ctr = SBenuCounters(matches_plus=self._count_plus,
                            matches_minus=self._count_minus)
        stats.extras.update(
            delta_plus=set(self._plus), delta_minus=set(self._minus),
            counters=ctr, per_shard_counts=self._per_shard,
            per_shard_level_sizes=(
                self._level_acc if self._level_acc is not None
                else np.zeros((0, self.S))),
            cold_rows_fetched=self._cold)


# --------------------------------------------------------------------------
# Factory + dry-run hook
# --------------------------------------------------------------------------


BACKENDS = {
    "ref": RefBackend,
    "jax": JaxBackend,
    "jax-gpu": JaxGpuBackend,
    "dist": DistBackend,
    "oocache": OocBackend,
    "sbenu": SBenuBackend,
    "sbenu-jax": SBenuJaxBackend,
    "sbenu-dist": SBenuDistBackend,
}


def make_executor(engine: str, **backend_kwargs) -> Executor:
    """``make_executor('dist', hot=64, rebalance=True).run(plan, graph)``."""
    try:
        cls = BACKENDS[engine]
    except KeyError:
        raise ValueError(
            f"unknown engine {engine!r}; choose from {sorted(BACKENDS)}")
    return Executor(cls(**backend_kwargs))


def build_benu_step(plan: Plan, spec, mesh, axis, caps: Sequence[int],
                    req_cap: int, rebalance: bool = True):
    """The distributed enumeration step the dry-run lowers for the BENU
    cell — the same step :class:`DistBackend` executes, exposed so
    launch/steps.py routes through the unified API."""
    from .engine_dist import build_distributed_step
    return build_distributed_step(plan, spec, mesh, axis, list(caps),
                                  req_cap, rebalance=rebalance)
