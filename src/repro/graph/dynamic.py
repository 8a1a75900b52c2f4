"""Dynamic directed data graph storage (paper §5, §6.2).

Maintains exactly the two snapshots S-BENU needs — ``G'_{t-1}`` and the
current delta sets — using the paper's two-form value design:

* between steps, a vertex value is ``(in_prev, out_prev)``;
* inside step t, touched vertices additionally carry
  ``(delta_in, delta_out)`` with per-edge flags ``{'+','-'}``.

``get_adj(v, type, direction, op)`` serves the six adjacency kinds of §5.3.1
for either snapshot; ``op='+'`` selects ``G'_t``, ``op='-'`` selects
``G'_{t-1}``, and ``(type='delta', op='*')`` returns the flagged delta set.

Six-adjacency device layout (the vectorized S-BENU substrate)
-------------------------------------------------------------
:meth:`SnapshotStore.device_snapshot` materializes the begun step as six
typed/directed padded row blocks — ``{out, in} x {prev, current, delta}`` —
each a sentinel-padded ``int32[N+1, D]`` matrix (row ``N`` is the all-holes
sentinel row so gathers with invalid ids are safe):

* ``prev_{out,in}``    rows of ``G'_{t-1}`` — serves ``(either, dir, '-')``;
* ``cur_{out,in}``     rows of ``G'_t``     — serves ``(either, dir, '+')``;
* ``delta_{out,in}``   the touched-vertex delta adjacency, value rows
  paired with ``delta_*_sign`` rows carrying the paper's ± edge flags
  (+1 insert, -1 delete, 0 hole).

The two remaining §5.3.1 kinds are derived lane-wise on device:
``unaltered = prev`` with entries flagged ``-`` masked out, and
``(delta, dir, ±)`` = the sign-filtered delta value rows. ``prev``/``cur``
blocks of one direction share a width so a per-row snapshot selector
(Delta-ENU's ``op``) is a plain ``where`` between two gathers.

:class:`DeviceSnapshotStore` keeps the resident blocks either on device
(``storage='device'``, the streaming fast path) or in host-RAM shards
(``storage='host'``, backed by :class:`~repro.graph.hoststore.HostRowStore`
— zero persistent HBM between steps, with bounded-device row serving via
:meth:`DeviceSnapshotStore.row_source` + the ``distributed/rowcache``
device cache for snapshots whose resident blocks would not fit HBM).

Example (two time steps; ``get_adj`` serves both snapshots)::

    >>> from repro.graph.storage import DiGraph
    >>> from repro.graph.dynamic import SnapshotStore
    >>> g0 = DiGraph.from_edges(4, [(0, 1), (1, 2)])
    >>> st = SnapshotStore(g0)
    >>> st.begin_step([("+", 2, 3), ("-", 0, 1)])
    >>> st.start_vertices()                  # vertices with non-empty dG_out
    [0, 2]
    >>> sorted(st.get_adj(2, "either", "out", "+"))   # G'_t
    [3]
    >>> sorted(st.get_adj(0, "either", "out", "-"))   # G'_{t-1}
    [1]
    >>> st.end_step()
    >>> sorted(st.prev.out[0])               # the merged snapshot
    []
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from .. import obs
from .storage import DiGraph, pad_rows

Update = Tuple[str, int, int]  # (op, src, dst)


@dataclass
class DeviceSnapshot:
    """The six padded adjacency blocks of one time step (numpy; the JAX
    engine registers this class as a pytree and moves it to device).

    All value blocks are sentinel-padded ``int32[N+1, D]`` with ascending
    valid entries; sign blocks are ``int32[N+1, Dd]`` aligned with
    ``delta_*`` (+1/-1, 0 at holes). ``n`` is the vertex count == sentinel.
    """

    prev_out: np.ndarray
    prev_in: np.ndarray
    cur_out: np.ndarray
    cur_in: np.ndarray
    delta_out: np.ndarray
    delta_out_sign: np.ndarray
    delta_in: np.ndarray
    delta_in_sign: np.ndarray
    n: int

    @property
    def d_out(self) -> int:
        return self.prev_out.shape[1]

    @property
    def d_in(self) -> int:
        return self.prev_in.shape[1]

    @property
    def widths(self) -> Tuple[int, ...]:
        """Static shape signature — equal widths mean no recompilation."""
        return (self.prev_out.shape[1], self.prev_in.shape[1],
                self.delta_out.shape[1], self.delta_in.shape[1])


def _with_sentinel_row(rows: np.ndarray, fill: int) -> np.ndarray:
    return np.concatenate(
        [rows, np.full((1, rows.shape[1]), fill, rows.dtype)], axis=0)


class SnapshotStore:
    """The paper's two-form vertex values for one dynamic graph (§5, §6.2).

    Holds ``prev`` (= G'_{t-1}, a :class:`DiGraph`) plus the begun step's
    delta adjacency dicts ``delta_out/delta_in`` (vertex -> {neighbor:
    '+'|'-'}). One ``begin_step(batch) ... end_step()`` bracket is one
    time step of Algorithm 4; between the two calls every §5.3.1
    adjacency kind of either snapshot is served by :meth:`get_adj`.
    """

    def __init__(self, g0: DiGraph):
        self.n = g0.n
        self.prev = g0.copy()           # G'_{t-1}
        self.delta_out: Dict[int, Dict[int, str]] = {}
        self.delta_in: Dict[int, Dict[int, str]] = {}
        self.t = 0
        # device-resident mirrors notified on end_step (DeviceSnapshotStore)
        self._mirrors: List["DeviceSnapshotStore"] = []

    # ------------------------------------------------------------ time steps
    def begin_step(self, batch: Sequence[Update]) -> None:
        """Convert Δo_t into delta adjacency sets (Alg. 4 lines 7-9)."""
        with obs.span("store.begin_step"):
            self.t += 1
            self.delta_out = {}
            self.delta_in = {}
            seen: Set[Tuple[int, int]] = set()
            for op, a, b in batch:
                if (a, b) in seen:
                    raise ValueError(f"edge ({a},{b}) appears twice in batch")
                seen.add((a, b))
                if op == "+" and self.prev.has_edge(a, b):
                    raise ValueError(f"inserting existing edge ({a},{b})")
                if op == "-" and not self.prev.has_edge(a, b):
                    raise ValueError(f"deleting missing edge ({a},{b})")
                self.delta_out.setdefault(a, {})[b] = op
                self.delta_in.setdefault(b, {})[a] = op

    def end_step(self) -> None:
        """Merge deltas into the stored snapshot (Alg. 4 line 21)."""
        with obs.span("store.end_step"):
            for a, dd in self.delta_out.items():
                for b, op in dd.items():
                    if op == "+":
                        self.prev.add_edge(a, b)
                    else:
                        self.prev.remove_edge(a, b)
            for m in self._mirrors:
                m.on_host_end_step()
            self.delta_out = {}
            self.delta_in = {}

    # --------------------------------------------------------------- queries
    def start_vertices(self) -> List[int]:
        """Vertices with non-empty ΔΓ_out (Alg. 4 line 10)."""
        return sorted(self.delta_out.keys())

    def delta_adj_out(self, v: int) -> List[Tuple[str, int]]:
        """ΔΓ_out(v) as ``[('+'|'-', neighbor)]`` sorted by neighbor id."""
        dd = self.delta_out.get(v, {})
        return sorted(((op, w) for w, op in dd.items()), key=lambda x: x[1])

    def get_adj(self, v: int, type_: str, direction: str,
                op: str) -> frozenset:
        """Γ^{type,direction}_{G'_?}(v); ``?`` = t if op=='+', t-1 if op=='-'."""
        prev = self.prev.out[v] if direction == "out" else self.prev.inn[v]
        dd = (self.delta_out if direction == "out" else self.delta_in
              ).get(v, {})
        inserted = {w for w, o in dd.items() if o == "+"}
        deleted = {w for w, o in dd.items() if o == "-"}
        unaltered = prev - deleted
        if type_ == "unaltered":
            return frozenset(unaltered)
        if type_ == "either":
            if op == "+":     # G'_t
                return frozenset(unaltered | inserted)
            return frozenset(prev)
        if type_ == "delta":
            if op == "+":
                return frozenset(inserted)
            return frozenset(deleted)
        raise ValueError(type_)

    # ------------------------------------------------------ device layout
    def device_snapshot(self, lane: int = 8,
                        d_min: int = 0, delta_d_min: int = 0
                        ) -> DeviceSnapshot:
        """Materialize the begun step as the six padded row blocks (host
        build, from scratch — the simple reference path; the streaming
        engine keeps a :class:`DeviceSnapshotStore` instead, which stays
        resident on device and advances incrementally).

        ``d_min``/``delta_d_min`` are width floors (rounded up to ``lane``):
        pinning them across time steps keeps the block shapes static so the
        JIT engine compiles once per stream instead of once per step.
        """
        n = self.n
        sets_by_dir = {"out": self.prev.out, "in": self.prev.inn}
        delta_by_dir = {"out": self.delta_out, "in": self.delta_in}
        blocks: Dict[str, np.ndarray] = {}
        for di in ("out", "in"):
            prev_sets = sets_by_dir[di]
            dd = delta_by_dir[di]
            prev_adj = [np.array(sorted(s), dtype=np.int64)
                        for s in prev_sets]
            cur_adj = list(prev_adj)
            for v, ops in dd.items():
                cur = set(prev_sets[v])
                for w, op in ops.items():
                    (cur.add if op == "+" else cur.discard)(w)
                cur_adj[v] = np.array(sorted(cur), dtype=np.int64)
            # prev/cur share a width so the per-row op selector is a where()
            d = max(max((len(a) for a in prev_adj), default=0),
                    max((len(a) for a in cur_adj), default=0), d_min)
            blocks[f"prev_{di}"] = _with_sentinel_row(
                pad_rows(prev_adj, n, d_max=d, lane=lane), n)
            blocks[f"cur_{di}"] = _with_sentinel_row(
                pad_rows(cur_adj, n, d_max=d, lane=lane), n)
            d_delta = max(max((len(ops) for ops in dd.values()), default=0),
                          delta_d_min)
            dvals = [np.zeros(0, dtype=np.int64)] * n
            dsigns: List[np.ndarray] = [np.zeros(0, dtype=np.int64)] * n
            for v, ops in dd.items():
                ws = sorted(ops)
                dvals[v] = np.array(ws, dtype=np.int64)
                dsigns[v] = np.array([1 if ops[w] == "+" else -1
                                      for w in ws], dtype=np.int64)
            vals = _with_sentinel_row(
                pad_rows(dvals, n, d_max=d_delta, lane=lane), n)
            signs = pad_rows(dsigns, 0, d_max=d_delta, lane=lane)
            # sign holes are 0 (pad_rows fills with its sentinel arg)
            blocks[f"delta_{di}"] = vals
            blocks[f"delta_{di}_sign"] = _with_sentinel_row(signs, 0)
        return DeviceSnapshot(n=n, **blocks)

    # ----------------------------------------------------------- test helpers
    def snapshot(self, which: str) -> DiGraph:
        """Materialize G'_t ('cur') or G'_{t-1} ('prev') — test oracle only."""
        if which == "prev":
            return self.prev.copy()
        g = self.prev.copy()
        for a, dd in self.delta_out.items():
            for b, op in dd.items():
                if op == "+":
                    g.add_edge(a, b)
                else:
                    g.remove_edge(a, b)
        return g


def stream_width_floors(g0: DiGraph, batches: Sequence[Sequence[Update]]
                        ) -> Tuple[int, int]:
    """``(d_min, delta_d_min)`` pinning snapshot widths over a whole known
    update stream, so the JIT engine compiles once instead of retracing
    whenever a step's max degree or delta degree drifts."""
    cur = g0.copy()
    d = max(max((len(s) for s in cur.out), default=0),
            max((len(s) for s in cur.inn), default=0))
    dd = 0
    for batch in batches:
        touched_out: Dict[int, int] = {}
        touched_in: Dict[int, int] = {}
        for op, a, b in batch:
            touched_out[a] = touched_out.get(a, 0) + 1
            touched_in[b] = touched_in.get(b, 0) + 1
            if op == "+":
                cur.add_edge(a, b)
            else:
                cur.remove_edge(a, b)
        dd = max(dd, max(touched_out.values(), default=0),
                 max(touched_in.values(), default=0))
        d = max(d, max((len(s) for s in cur.out), default=0),
                max((len(s) for s in cur.inn), default=0))
    return d, dd


class DeviceSnapshotStore:
    """Device-resident dual-snapshot row store (the streaming fast path).

    Keeps the ``prev`` blocks resident on device across time steps and
    advances them incrementally, so per-step host work and upload are
    O(|ΔE|) instead of an O(N) Python rebuild:

    * :meth:`step_snapshot` (store begun): pack the update batch into
      touched rows (vectorized COO build: ids ``[K]``, values and signs
      ``[K, Dd]``, K the touched count padded to a power of two), send
      only those up, then in one device program per direction derive
      ``G'_t`` **touched rows only** — gather the K prev rows, mask
      deleted entries, merge the inserted delta values (concat + row sort
      + slice back to width D — the merged row fits by the width guard),
      and scatter them into a copy of the prev block — and scatter the
      touched rows into fresh dense ``delta`` / ``delta_sign`` blocks.
      Per-step device cost is O(|ΔV|·D) plus O(N·D) of copies and fills,
      not a full-graph masked sort.
    * end_step (via the :class:`SnapshotStore` mirror hook): the merged
      snapshot IS the cur block, so promotion is free buffer adoption
      (``prev <- cur``). Width overflow drops the mirror; the next step
      rebuilds with wider rows.

    Rebuild triggers (all O(N), rare): first use, a touched row outgrowing
    the pinned width, or the host store advancing without this mirror
    (e.g. interpreter steps in between).

    ``storage`` selects where the resident ``prev`` blocks live:

    * ``'device'`` (default): jax arrays on device — fastest per step, but
      the dual snapshot must fit HBM;
    * ``'host'``: :class:`~repro.graph.hoststore.HostRowStore` shards in
      host RAM, advanced **in place** by patching only the touched rows at
      ``end_step`` (O(|ΔV|·D) host work — no O(N) rebuild, no persistent
      device residency). :meth:`step_snapshot` still materializes full
      numpy blocks for the resident jit engine (compat path, transferred
      per step and freed after); :meth:`row_source` serves per-row
      ``prev``/``cur`` views for the bounded-device cache fetch path
      (``distributed/rowcache.py``) so row serving never needs the full
      block on device.
    """

    def __init__(self, store: SnapshotStore, lane: int = 8,
                 d_min: int = 0, delta_d_min: int = 0,
                 storage: str = "device"):
        import jax
        import jax.numpy as jnp
        if storage not in ("device", "host"):
            raise ValueError(f"storage must be device|host, got {storage!r}")
        self.host = store
        self.n = store.n
        self.storage = storage
        self.params = (lane, d_min, delta_d_min, storage)
        self.lane, self.d_min, self.delta_d_min = lane, d_min, delta_d_min
        self._jnp = jnp
        # device blocks carry this many rows: n real + 1 sentinel (the
        # mesh-sharded subclass pads further so shards divide evenly; rows
        # beyond n are all-sentinel and never gathered — clip(ids, 0, n))
        self._rows_total = store.n + 1
        # di -> jax [N+1, D] (device mode) | HostRowStore (host mode)
        self._prev: Optional[Dict[str, object]] = None
        self._d: Dict[str, int] = {}
        self._cur: Dict[str, object] = {}
        # host mode: di -> (touched ids int64[K], merged rows int32[K, D])
        self._cur_host: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
        self._pending_t: Optional[int] = None
        # this mirror's rebuilds; obs counter snapshot.rebuilds counts
        # them over the process
        self.rebuilds = 0
        # (prev, touched-row values) shapes derive has been called with
        self._derive_shapes: Set[Tuple[Tuple[int, ...], ...]] = set()

        def derive(prev, tids, kvals, ksigns):
            """``(cur, delta, delta_sign)`` blocks from prev + the touched
            rows' delta (``tids [K]`` sentinel-padded, ``kvals`` /
            ``ksigns [K, Dd]`` holes ``n`` / 0: padding rewrites the
            sentinel row with itself). Merged rows stay sorted with tail
            holes, so the engines' binary-search intersect b-side
            invariant holds."""
            with jax.named_scope("derive"):
                n = self.n
                d = prev.shape[1]
                rows = prev[tids]                       # [K, D]
                deleted = jnp.where(ksigns < 0, kvals, n)
                hit = jnp.any(rows[:, :, None] == deleted[:, None, :], axis=2)
                unalt = jnp.where(hit, n, rows)
                plus = jnp.where(ksigns > 0, kvals, n)
                merged = jnp.sort(jnp.concatenate([unalt, plus], axis=1),
                                  axis=1)[:, :d]        # fits: width guard
                dense = (prev.shape[0], kvals.shape[1])
                return (prev.at[tids].set(merged),
                        jnp.full(dense, n, jnp.int32).at[tids].set(kvals),
                        jnp.zeros(dense, jnp.int32).at[tids].set(ksigns))

        self._derive_fn = derive
        self._derive = jax.jit(derive)
        store._mirrors.append(self)

    def _place(self, arr: np.ndarray):
        """Device placement of one block (subclass hook: the mesh-sharded
        store device_puts with a row-partitioned NamedSharding here)."""
        return self._jnp.asarray(arr)

    def _place_touched(self, arr: np.ndarray):
        """Device placement of one step's touched-row array (subclass
        hook: the mesh-sharded store replicates it, as K need not divide
        by the mesh)."""
        return self._jnp.asarray(arr)

    @classmethod
    def for_store(cls, store: SnapshotStore, lane: int = 8,
                  d_min: int = 0, delta_d_min: int = 0,
                  storage: str = "device") -> "DeviceSnapshotStore":
        """Reuse an existing mirror with the same layout parameters."""
        for m in store._mirrors:
            if isinstance(m, cls) and m.params == (lane, d_min,
                                                   delta_d_min, storage):
                return m
        return cls(store, lane=lane, d_min=d_min, delta_d_min=delta_d_min,
                   storage=storage)

    def _round(self, x: int) -> int:
        return ((max(x, 1) + self.lane - 1) // self.lane) * self.lane

    def _rebuild_prev(self) -> None:
        """Full host build of the resident prev blocks (stream start or
        width overflow); accounts for this step's inserts so cur fits.
        Device mode materializes jax ``[N+1, D]`` blocks; host mode builds
        :class:`HostRowStore` shards (one shard transient at a time)."""
        from .hoststore import HostRowStore
        self.rebuilds += 1
        obs.count("snapshot.rebuilds")
        n = self.n
        self._prev = {}
        for di, sets, delta in (("out", self.host.prev.out,
                                 self.host.delta_out),
                                ("in", self.host.prev.inn,
                                 self.host.delta_in)):
            need = max((len(sets[v])
                        + sum(1 for op in ops.values() if op == "+")
                        for v, ops in delta.items()), default=0)
            d = self._round(max(max((len(s) for s in sets), default=0),
                                need, self.d_min))
            if self.storage == "host":
                self._prev[di] = HostRowStore.from_adj(
                    lambda v: sorted(sets[v]), n, d)
            else:
                rows = np.full((self._rows_total, d), n, np.int32)
                for v, s in enumerate(sets):
                    a = sorted(s)
                    rows[v, :len(a)] = a
                with obs.span("snapshot.place"):
                    obs.count("snapshot.h2d_bytes", rows.nbytes)
                    self._prev[di] = self._place(rows)
            self._d[di] = d

    def _delta_rows(self) -> Dict[str, Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]]:
        """The begun step's delta as touched rows, per direction (COO
        build in one pass over ΔΓ_out: ΔΓ_in holds the same edges
        reversed). O(|ΔE|) host work, whatever N."""
        with obs.span("snapshot.delta_buffers"):
            items = [(v, w, 1 if op == "+" else -1)
                     for v, ops in self.host.delta_out.items()
                     for w, op in ops.items()]
            e = np.fromiter(itertools.chain.from_iterable(items), np.int64,
                            3 * len(items)).reshape(-1, 3)
            return {"out": self._touched_rows(e[:, 0], e[:, 1], e[:, 2]),
                    "in": self._touched_rows(e[:, 1], e[:, 0], e[:, 2])}

    def _touched_rows(self, src: np.ndarray, dst: np.ndarray,
                      sign: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(tids int32[K], vals int32[K, Dd], signs int32[K, Dd])`` of
        the edges ``src -> dst``: ids ascending then sentinel-padded to a
        power of two K, so steps with similar churn share one compiled
        derive shape; each row's values ascending, value holes ``n``,
        sign holes 0."""
        n = self.n
        order = np.lexsort((dst, src))
        src, dst, sign = src[order], dst[order], sign[order]
        gstart = np.flatnonzero(np.diff(src, prepend=-1))
        counts = np.diff(gstart, append=len(src))
        row = np.repeat(np.arange(len(gstart)), counts)
        pos = np.arange(len(src)) - gstart[row]
        k = 1 << max(len(gstart) - 1, 0).bit_length()
        dd = self._round(max(int(counts.max(initial=0)), self.delta_d_min))
        tids = np.full(k, n, np.int32)
        tids[:len(gstart)] = src[gstart]
        vals = np.full((k, dd), n, np.int32)
        signs = np.zeros((k, dd), np.int32)
        vals[row, pos] = dst
        signs[row, pos] = sign
        return tids, vals, signs

    def _derive_host(self, store, delta: Dict[int, Dict[int, str]]
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side merge of the touched rows: ``(tids int64[K],
        merged int32[K, D])`` — G'_t rows for exactly the touched
        vertices, O(|ΔV|·D) work (the numpy twin of the device
        ``derive``)."""
        n = self.n
        touched = np.asarray(sorted(delta), np.int64)
        if touched.size == 0:
            return touched, np.zeros((0, store.d), np.int32)
        rows = store.gather(touched)
        for i, v in enumerate(touched):
            ops = delta[int(v)]
            cur = {int(x) for x in rows[i] if x != n}
            for w, op in ops.items():
                (cur.add if op == "+" else cur.discard)(w)
            a = sorted(cur)
            rows[i] = n
            rows[i, :len(a)] = a       # fits: step_snapshot width guard
        return touched, rows

    def _ensure_prev_fits(self) -> None:
        """Width guard shared by every per-step entry point: a touched row
        of G'_t outgrowing the pinned width forces a wider rebuild
        (deletes only shrink rows)."""
        st = self.host
        with obs.span("snapshot.fit"):
            if self._prev is not None:
                for di, sets, delta in (("out", st.prev.out, st.delta_out),
                                        ("in", st.prev.inn, st.delta_in)):
                    if any(len(sets[v]) + sum(1 for op in ops.values()
                                              if op == "+") > self._d[di]
                           for v, ops in delta.items()):
                        self._prev = None
                        break
            if self._prev is None:
                with obs.span("snapshot.rebuild"):
                    self._rebuild_prev()

    def _ensure_step_cur_host(self) -> None:
        """Host mode: derive (and cache) both directions' merged touched
        rows for the begun step, once per step — row_source() and
        step_snapshot() share this state, and setting ``_pending_t``
        makes ``end_step`` patch the shards in place instead of
        discarding them."""
        st = self.host
        self._ensure_prev_fits()
        if self._pending_t == st.t and len(self._cur_host) == 2:
            return
        self._cur_host = {
            di: self._derive_host(self._prev[di], delta)
            for di, delta in (("out", st.delta_out), ("in", st.delta_in))}
        self._pending_t = st.t

    def step_snapshot(self) -> DeviceSnapshot:
        """Six blocks for the host store's begun step, derived on device."""
        st = self.host
        if self.storage == "host":
            # host mode: merge touched rows on host (O(|ΔV|·D)), assemble
            # numpy blocks for the resident engine (compat path — the
            # bounded-device path serves rows via row_source() instead)
            self._ensure_step_cur_host()
            blocks_h: Dict[str, np.ndarray] = {}
            for di, (rids, kvals, ksigns) in self._delta_rows().items():
                dense = (self._rows_total, kvals.shape[1])
                vals = np.full(dense, self.n, np.int32)
                signs = np.zeros(dense, np.int32)
                vals[rids], signs[rids] = kvals, ksigns
                hs = self._prev[di]
                tids, merged = self._cur_host[di]
                prev_full = hs.to_rows()
                cur_full = prev_full.copy()
                if tids.size:
                    cur_full[tids] = merged
                blocks_h[f"prev_{di}"] = prev_full
                blocks_h[f"cur_{di}"] = cur_full
                blocks_h[f"delta_{di}"] = vals
                blocks_h[f"delta_{di}_sign"] = signs
            return DeviceSnapshot(n=self.n, **blocks_h)
        self._ensure_prev_fits()
        blocks: Dict[str, object] = {}
        for di, touched in self._delta_rows().items():
            with obs.span("snapshot.place"):
                obs.count("snapshot.h2d_bytes",
                          sum(a.nbytes for a in touched))
                args = [self._place_touched(a) for a in touched]
            prev = self._prev[di]
            shapes = (prev.shape, touched[1].shape)
            with obs.span("snapshot.derive"):
                if shapes in self._derive_shapes:
                    cur, jvals, jsigns = self._derive(prev, *args)
                else:
                    # a new shape: tracing, compiling, first dispatch
                    self._derive_shapes.add(shapes)
                    with obs.span("jit.build"):
                        obs.count("jit.builds")
                        cur, jvals, jsigns = self._derive(prev, *args)
            self._cur[di] = cur
            blocks[f"prev_{di}"] = self._prev[di]
            blocks[f"cur_{di}"] = cur
            blocks[f"delta_{di}"] = jvals
            blocks[f"delta_{di}_sign"] = jsigns
        self._pending_t = st.t
        return DeviceSnapshot(n=self.n, **blocks)

    def on_host_end_step(self) -> None:
        """SnapshotStore mirror hook (post-merge): promote cur -> prev.

        Device mode adopts the derived cur buffers; host mode patches the
        touched rows back into the host shards in place (O(|ΔV|·D))."""
        st = self.host
        if self._prev is None:
            return
        if self._pending_t != st.t:
            self._prev = None            # store advanced without us
            return
        for di, sets, delta in (("out", st.prev.out, st.delta_out),
                                ("in", st.prev.inn, st.delta_in)):
            if any(len(sets[v]) > self._d[di] for v in delta):
                self._prev = None        # merged row overflows: rebuild
                return
        if self.storage == "host":
            for di in ("out", "in"):
                tids, merged = self._cur_host.get(
                    di, (np.zeros(0, np.int64), None))
                if tids.size:
                    self._prev[di].set_rows(tids, merged)
            self._cur_host = {}
            self._pending_t = None
            return
        for di in ("out", "in"):
            self._prev[di] = self._cur[di]   # promotion is buffer adoption
        self._cur = {}
        self._pending_t = None

    # ------------------------------------------------- bounded row serving
    def row_source(self, direction: str, which: str = "cur"
                   ) -> "SnapshotRowView":
        """A :class:`HostRowStore`-shaped view over one resident block.

        Host mode only (device mode already has the block resident).
        ``which='prev'`` serves G'_{t-1} rows straight from the shards;
        ``which='cur'`` overlays the begun step's merged touched rows.
        Feed the view to ``distributed.rowcache.DeviceRowCache`` to serve
        snapshot rows with bounded device residency — the fetch path for
        streams whose resident blocks would not fit HBM.

        Coherence across steps: ``end_step`` patches the backing shards
        **in place**, so a ``DeviceRowCache`` kept alive across steps
        must be told — call ``cache.invalidate(touched_ids)`` after
        ``end_step`` (only ``'prev'`` views are meaningful to keep; a
        ``'cur'`` view's overlay is per-step by construction, so request
        a fresh one via this method each step). A *rebuild* of the
        resident shards (``self.rebuilds`` increments: width overflow,
        or the host store advancing without this mirror) replaces the
        backing store wholesale — rebuild any long-lived cache when that
        counter changes. The view itself always resolves the mirror's
        current store, so it never serves an orphaned pre-rebuild copy.
        """
        if self.storage != "host":
            raise ValueError("row_source() requires storage='host'")
        if which == "prev":
            self._ensure_prev_fits()
            return SnapshotRowView(self, direction, {})
        if which != "cur":
            raise ValueError(f"which must be prev|cur, got {which!r}")
        # derives once per step (both directions) and marks the step
        # pending, so end_step patches the shards in place — the bounded
        # path gets the same O(|ΔV|·D) advance as step_snapshot users
        self._ensure_step_cur_host()
        tids, merged = self._cur_host[direction]
        return SnapshotRowView(
            self, direction,
            {int(v): merged[i] for i, v in enumerate(tids)})


@dataclass(frozen=True)
class SnapshotShardSpec:
    """Static layout of a mesh-sharded six-block snapshot.

    Duck-compatible with the ``distributed/rowstore.py`` fetch builder
    (``n`` / ``n_shards`` / ``rows_per_shard`` / ``hot``): every block is
    block-partitioned by row over the enumeration axis (owner of row v =
    ``v // rows_per_shard``), widths vary per block and are read from the
    arrays at trace time. The ``hot`` highest ids (``>= n - hot``) are
    additionally replicated on every device and served locally. Note:
    unlike the static path, streaming graphs are **not** degree-relabeled
    at load, so the replicated set is an id range, only a hub set if the
    stream's vertex numbering makes it one — relabel the initial graph
    (and stream) by ascending degree to get the static engine's anti-skew
    behavior.
    """

    n: int                 # real vertices; sentinel value
    n_shards: int
    rows_per_shard: int    # ceil((n+1) / n_shards); blocks carry S*rps rows
    hot: int = 0


class ShardedDeviceSnapshotStore(DeviceSnapshotStore):
    """Mesh-sharded resident dual-snapshot store (the distributed
    streaming substrate, core/engine_sbenu_dist.py).

    Same per-step contract as the device-mode base class — resident
    ``prev`` blocks advanced incrementally, ``cur`` derived from
    ``prev`` + delta for the touched rows only, promotion by buffer
    adoption at ``end_step`` — but every block is laid out with
    ``S * rows_per_shard`` rows and device_put with a row-partitioned
    ``NamedSharding`` over the enumeration mesh, so the dual snapshot's
    HBM footprint is split S ways and the per-step derive runs as one
    GSPMD program over the sharded buffers.

    :meth:`step_sharded` additionally materializes the per-direction
    **joint delta block** (values ++ signs, one fetch per delta DBQ) and
    the replicated hot-row slices the SPMD engine serves locally.

    Snapshots from this store feed the ``shard_map`` engine; they are
    *not* interchangeable with the single-device engine's snapshots (row
    counts differ from ``n + 1`` — gathers still work, but there is no
    point paying the mesh layout without the mesh).
    """

    def __init__(self, store: SnapshotStore, mesh, axis: str = "shard",
                 lane: int = 8, d_min: int = 0, delta_d_min: int = 0,
                 hot: int = 0):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        self.mesh, self.axis = mesh, axis
        self.S = int(mesh.devices.size)
        super().__init__(store, lane=lane, d_min=d_min,
                         delta_d_min=delta_d_min, storage="device")
        self.rows_per_shard = -(-(store.n + 1) // self.S)
        self._rows_total = self.S * self.rows_per_shard
        self.hot = min(int(hot), store.n)
        self._jax = jax
        self._sh2d = NamedSharding(mesh, PartitionSpec(axis, None))
        self._rep = NamedSharding(mesh, PartitionSpec())
        # re-jit the shared derive with the row-partitioned output layout
        # (cur and the dense delta blocks alike)
        self._derive = jax.jit(self._derive_fn, out_shardings=self._sh2d)
        self.params = (lane, d_min, delta_d_min, "sharded", self.S,
                       axis, self.hot)

    @classmethod
    def for_store(cls, store: SnapshotStore, mesh, axis: str = "shard",
                  lane: int = 8, d_min: int = 0, delta_d_min: int = 0,
                  hot: int = 0) -> "ShardedDeviceSnapshotStore":
        """Reuse an existing sharded mirror with the same layout + mesh."""
        key = (lane, d_min, delta_d_min, "sharded", int(mesh.devices.size),
               axis, min(int(hot), store.n))
        for m in store._mirrors:
            if isinstance(m, cls) and m.params == key and m.mesh is mesh:
                return m
        return cls(store, mesh, axis=axis, lane=lane, d_min=d_min,
                   delta_d_min=delta_d_min, hot=hot)

    def _place(self, arr: np.ndarray):
        return self._jax.device_put(np.asarray(arr), self._sh2d)

    def _place_touched(self, arr: np.ndarray):
        return self._jax.device_put(arr, self._rep)

    def step_sharded(self) -> Tuple[Dict[str, object], Dict[str, object],
                                    SnapshotShardSpec]:
        """``(blocks, hot_blocks, spec)`` for the begun step.

        ``blocks``: six row-partitioned device arrays — ``prev_/cur_{out,
        in}`` plus ``delta_joint_{out,in}`` (values ++ signs concatenated
        along the width, so one request/response exchange serves a whole
        flagged delta row). ``hot_blocks``: the replicated ``[hot+1, W]``
        top-id slices of each (the ``+1`` is the sentinel row, matching
        ``distributed/rowstore.py``).
        """
        jnp = self._jnp
        snap = self.step_snapshot()
        blocks: Dict[str, object] = {
            "prev_out": snap.prev_out, "cur_out": snap.cur_out,
            "prev_in": snap.prev_in, "cur_in": snap.cur_in,
            "delta_joint_out": self._jax.device_put(
                jnp.concatenate([snap.delta_out, snap.delta_out_sign],
                                axis=1), self._sh2d),
            "delta_joint_in": self._jax.device_put(
                jnp.concatenate([snap.delta_in, snap.delta_in_sign],
                                axis=1), self._sh2d),
        }
        lo = self.n - self.hot
        hot_blocks = {k: self._jax.device_put(v[lo:self.n + 1], self._rep)
                      for k, v in blocks.items()}
        spec = SnapshotShardSpec(n=self.n, n_shards=self.S,
                                 rows_per_shard=self.rows_per_shard,
                                 hot=self.hot)
        return blocks, hot_blocks, spec


class SnapshotRowView:
    """Read-only ``HostRowStore``-API view over one direction of a
    host-mode :class:`DeviceSnapshotStore`, plus per-step row patches.

    Duck-types the three members ``DeviceRowCache`` needs (``n``, ``d``,
    ``gather``); ``patches`` maps vertex id -> replacement row
    (``int32[d]``, sentinel-padded). The backing shards are resolved
    through the mirror on every access, so a width rebuild swaps in the
    new store here transparently (callers holding a ``DeviceRowCache``
    over the view still need to rebuild it then — the cached row width
    changes; see :meth:`DeviceSnapshotStore.row_source`).
    """

    def __init__(self, mirror: "DeviceSnapshotStore", direction: str,
                 patches: Dict[int, np.ndarray]):
        self.mirror = mirror
        self.direction = direction
        self.patches = patches
        self.n = mirror.n

    @property
    def base(self):
        return self.mirror._prev[self.direction]

    @property
    def d(self) -> int:
        return self.base.d

    def gather(self, ids: np.ndarray) -> np.ndarray:
        """Dense ``int32[K, d]`` rows with patches applied (clip
        semantics identical to :meth:`HostRowStore.gather`)."""
        out = self.base.gather(ids)
        if self.patches:
            flat = np.clip(np.asarray(ids, np.int64).reshape(-1), 0, self.n)
            for i, v in enumerate(flat):
                p = self.patches.get(int(v))
                if p is not None:
                    out[i] = p
        return out
