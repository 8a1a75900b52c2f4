"""Seeded, vectorised data generators for the benchmark's deployments.

Everything here is numpy only and depends on nothing of the program under
test, so the same seed gives the same graph, stream and task order on any
machine. Two generators:

* :func:`static_graph` - an undirected power-law graph with triadic
  closure (Chung-Lu edges plus closed wedges), degrees capped at a fixed
  row width, relabelled by ``(degree, id)`` ascending as the system's
  loader orders vertices. Returned as CSR: ``indptr int64[n+1]`` and
  sorted ``indices int64[2m]``.
* :func:`edge_stream` - a directed graph with power-law endpoints and a
  stream of update batches (inserts of absent edges, deletes of present
  ones, each edge at most once per batch), as ``(op, src, dst)`` arrays.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np


def _unique_keys(a: np.ndarray, b: np.ndarray, n: int) -> np.ndarray:
    """Undirected edge keys ``lo * n + hi`` without self loops, first
    occurrence kept, in order of appearance."""
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    keys = (lo * n + hi)[lo != hi]
    _, first = np.unique(keys, return_index=True)
    return keys[np.sort(first)]


def _csr(n: int, src: np.ndarray, dst: np.ndarray
         ) -> Tuple[np.ndarray, np.ndarray]:
    """CSR of a symmetric edge list (``src``/``dst`` hold both directions,
    no pair twice); each row's neighbours ascend."""
    keys = np.sort(src * n + dst)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, keys % n


def _cap_degrees(rng: np.random.Generator, n: int, keys: np.ndarray,
                 cap: int) -> np.ndarray:
    """Drop edges until no vertex has more than ``cap``: every edge gets a
    random priority and survives when it ranks below ``cap`` among the
    edges of each of its endpoints."""
    m = keys.shape[0]
    ends = np.concatenate([keys // n, keys % n])
    edge = np.concatenate([np.arange(m), np.arange(m)])
    pri = np.tile(rng.permutation(m), 2)
    order = np.argsort(ends * m + pri)
    e = ends[order]
    starts = np.flatnonzero(np.r_[True, e[1:] != e[:-1]])
    rank = np.arange(e.shape[0]) - np.repeat(
        starts, np.diff(np.r_[starts, e.shape[0]]))
    keep = np.ones(m, bool)
    keep[edge[order[rank >= cap]]] = False
    return keys[keep]


def _top_up(rng: np.random.Generator, n: int, keys: np.ndarray,
            cap: int) -> np.ndarray:
    """Give the vertex of highest degree exactly ``cap`` neighbours, so
    that the padded row width is ``cap`` for every seed."""
    a, b = keys // n, keys % n
    deg = np.bincount(a, minlength=n) + np.bincount(b, minlength=n)
    hub = int(np.argmax(deg))
    need = cap - int(deg[hub])
    if need <= 0:
        return keys
    nbrs = np.concatenate([b[a == hub], a[b == hub]])
    cand = rng.permutation(n)
    ok = (cand != hub) & (deg[cand] < cap) & ~np.isin(cand, nbrs)
    new = cand[ok][:need]
    return np.concatenate([keys, np.minimum(new, hub) * n
                           + np.maximum(new, hub)])


def _powerlaw_cdf(n: int, gamma: float, ratio: float) -> np.ndarray:
    """Cumulative endpoint probabilities ``w_i ~ (i + i0)^(-1 / (gamma -
    1))``, with ``i0`` set by bisection so that ``w_0 / mean(w)`` is
    ``ratio``: vertex 0 is the largest hub."""
    alpha = 1.0 / (gamma - 1.0)
    lo, hi = 1e-3, float(n)
    for _ in range(60):
        i0 = (lo * hi) ** 0.5
        w = (np.arange(n) + i0) ** -alpha
        if w[0] / w.mean() > ratio:
            lo = i0
        else:
            hi = i0
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def static_graph(seed: int, n: int, avg_degree: float, gamma: float,
                 max_degree: int, closure_share: float,
                 hub_overshoot: float, label_seed: int = 0
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Power-law graph with triadic closure, as CSR ``(indptr, indices)``.

    ``n * avg_degree / 2`` edges are aimed at: ``1 - closure_share`` of
    them Chung-Lu edges with expected degrees ``w_i ~ (i + i0)^(-1 /
    (gamma - 1))``, scaled so that the largest weight is ``hub_overshoot
    * max_degree``; the rest close random wedges (a random edge ``(u, v)``
    and a random neighbour ``w`` of ``v`` give the edge ``(u, w)``).
    Degrees are then capped at ``max_degree``, the highest one topped up
    to exactly ``max_degree``, and vertices relabelled by degree
    ascending, ties broken in an order drawn from ``label_seed``: graphs
    of one ``seed`` and different ``label_seed`` are isomorphic.
    """
    rng = np.random.default_rng(seed)
    m_target = int(round(n * avg_degree / 2))
    m_cl = int(round(m_target * (1.0 - closure_share)))
    cdf = _powerlaw_cdf(n, gamma, hub_overshoot * max_degree
                        / (2.0 * m_cl / n))
    keys = np.zeros(0, np.int64)
    while keys.shape[0] < m_cl:
        k = int((m_cl - keys.shape[0]) * 1.1) + 1024
        a = np.searchsorted(cdf, rng.random(k))
        b = np.searchsorted(cdf, rng.random(k))
        keys = _unique_keys(np.concatenate([keys // n, a]),
                            np.concatenate([keys % n, b]), n)
    keys = keys[:m_cl]
    # triadic closure over the Chung-Lu edges
    src = np.concatenate([keys // n, keys % n])
    dst = np.concatenate([keys % n, keys // n])
    indptr, indices = _csr(n, src, dst)
    deg = np.diff(indptr)
    m_tc = m_target - m_cl
    while keys.shape[0] < m_target:
        k = int((m_target - keys.shape[0]) * 1.3) + 1024
        e = rng.integers(0, src.shape[0], k)
        u, v = src[e], dst[e]
        w = indices[indptr[v] + (rng.random(k) * deg[v]).astype(np.int64)]
        before = keys.shape[0]
        keys = _unique_keys(np.concatenate([keys // n, u]),
                            np.concatenate([keys % n, w]), n)
        if keys.shape[0] == before:
            break
    keys = keys[:m_cl + m_tc]
    keys = _cap_degrees(rng, n, keys, max_degree)
    keys = _top_up(rng, n, keys, max_degree)
    a, b = keys // n, keys % n
    deg = np.bincount(a, minlength=n) + np.bincount(b, minlength=n)
    tie = np.random.default_rng([label_seed, 2]).permutation(n)
    order = np.lexsort((tie, deg))            # degree ascending
    relabel = np.empty(n, np.int64)
    relabel[order] = np.arange(n)
    a, b = relabel[a], relabel[b]
    return _csr(n, np.concatenate([a, b]), np.concatenate([b, a]))


@dataclass
class Stream:
    """A directed initial graph and its update batches.

    ``g0_src``/``g0_dst``: the initial edges. ``ops[t]``, ``src[t]``,
    ``dst[t]``: batch ``t`` as ``int8`` (+1 insert, -1 delete) and vertex
    ids. ``d_out``/``d_in``: the largest out- and in-degree the graph
    reaches over the whole stream; ``dd_out``/``dd_in``: the largest
    number of updates one vertex sees as source / target in one batch.
    """

    n: int
    g0_src: np.ndarray
    g0_dst: np.ndarray
    ops: List[np.ndarray]
    src: List[np.ndarray]
    dst: List[np.ndarray]
    d_out: int
    d_in: int
    dd_out: int
    dd_in: int


def edge_stream(seed: int, n: int, m0: int, steps: int, batch: int,
                delete_share: float, gamma: float, hub_degree: float
                ) -> Stream:
    """A directed Chung-Lu graph of ``m0`` edges and ``steps`` batches of
    ``batch`` updates. Both endpoints of an edge are drawn with
    probability ``~ (i + i0)^(-1 / (gamma - 1))``, the largest hub
    expecting ``hub_degree`` out- (and in-) neighbours;
    ``round(batch * delete_share)`` updates of a batch delete present
    edges drawn uniformly, the rest insert absent edges drawn like the
    initial ones. The whole stream is known in advance."""
    rng = np.random.default_rng(seed)
    cdf = _powerlaw_cdf(n, gamma, hub_degree / (m0 / n))

    def ends(k: int) -> Tuple[np.ndarray, np.ndarray]:
        return (np.searchsorted(cdf, rng.random(k)),
                np.searchsorted(cdf, rng.random(k)))
    keys = np.zeros(0, np.int64)
    while keys.shape[0] < m0:
        k = int((m0 - keys.shape[0]) * 1.05) + 1024
        a, b = ends(k)
        new = (a * n + b)[a != b]
        keys = np.concatenate([keys, new])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]
    keys = keys[:m0]
    g0 = np.sort(keys)
    g0_src, g0_dst = g0 // n, g0 % n
    out_deg = np.bincount(g0_src, minlength=n)
    in_deg = np.bincount(g0_dst, minlength=n)
    d_out, d_in = int(out_deg.max()), int(in_deg.max())
    dd_out = dd_in = 0
    n_del = int(round(batch * delete_share))
    n_ins = batch - n_del
    # the present edges, in no order: a batch's deletes leave holes that
    # its first inserts fill, the rest are appended, so no step copies
    # the whole edge list
    pool = np.empty(m0 + steps * max(n_ins - n_del, 0), np.int64)
    pool[:m0] = keys
    size = m0
    present = set(keys.tolist())
    ops, srcs, dsts = [], [], []
    for _ in range(steps):
        del_idx = rng.choice(size, n_del, replace=False)
        dels = pool[del_idx]
        ins: List[int] = []
        fresh = set()
        while len(ins) < n_ins:
            a, b = ends(2 * n_ins)
            for c in (a * n + b)[a != b].tolist():
                if c not in present and c not in fresh:
                    fresh.add(c)
                    ins.append(c)
                    if len(ins) == n_ins:
                        break
        insa = np.asarray(ins, np.int64)
        upd = np.concatenate([insa, dels])
        op = np.concatenate([np.ones(n_ins, np.int8),
                             -np.ones(n_del, np.int8)])
        perm = rng.permutation(upd.shape[0])
        upd, op = upd[perm], op[perm]
        s, d = upd // n, upd % n
        ops.append(op)
        srcs.append(s)
        dsts.append(d)
        present.difference_update(dels.tolist())
        present.update(fresh)
        k = min(n_del, n_ins)
        pool[del_idx[:k]] = insa[:k]
        if n_ins > n_del:
            pool[size:size + n_ins - n_del] = insa[k:]
        else:
            gone = np.zeros(size, bool)
            gone[del_idx[k:]] = True
            rest = pool[:size][~gone]
            pool[:rest.shape[0]] = rest
        size += n_ins - n_del
        np.add.at(out_deg, s, op)
        np.add.at(in_deg, d, op)
        d_out = max(d_out, int(out_deg.max()))
        d_in = max(d_in, int(in_deg.max()))
        dd_out = max(dd_out, int(np.bincount(s).max()))
        dd_in = max(dd_in, int(np.bincount(d).max()))
    return Stream(n=n, g0_src=g0_src, g0_dst=g0_dst, ops=ops, src=srcs,
                  dst=dsts, d_out=d_out, d_in=d_in, dd_out=dd_out,
                  dd_in=dd_in)


def task_order(seed: int, n: int, task_size: int) -> np.ndarray:
    """Start vertices in a seeded random order over all of V, cut into
    tasks: ``int64[n_tasks, task_size]``, padding ``-1``.

    The order is stratified: ids (ranked by degree) fall into
    ``task_size`` consecutive bands of ``n_tasks`` ids, and every task
    takes one vertex of each band, drawn at random. So every task, and any
    prefix of the order, is a sample of V across the whole degree range.
    """
    n_tasks = -(-n // task_size)
    ids = np.full(n_tasks * task_size, -1, np.int64)
    ids[:n] = np.arange(n)
    rng = np.random.default_rng([seed, 1])
    bands = rng.permuted(ids.reshape(task_size, n_tasks), axis=1)
    return bands.T[rng.permutation(n_tasks)].copy()
