"""Plain reference answers, independent of the program under test.

Ownership of a static match: the system enumerates each k-clique once,
as the tuple of its vertices in ascending id order (ids follow the
``(degree, id)`` ranking the generator gives them), and the task whose
start vertex is the clique's smallest vertex finds it. So the reference
count of a start vertex ``v`` is the number of k-cliques whose smallest
vertex is ``v``; a task's count is the sum over its start vertices.

Streaming answers: for the directed 3-cycle ``q1'`` (``a -> b -> c -> a``)
a match is reported once, as the rotation that puts its smallest vertex
first. A batch's ΔR+ is the set of matches of the graph after the batch
that use at least one inserted edge; ΔR- the set of matches of the graph
before the batch that use at least one deleted edge. Matches are encoded
as ``int64`` keys ``(f0 * n + f1) * n + f2``.
"""

from __future__ import annotations

from typing import Iterator, List, Set, Tuple

import numpy as np

#: wedges checked per block in :func:`clique_counts`; bounds host memory
BLOCK_WEDGES = 1 << 23


def _ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenation of ``arange(s, s + l)`` for every pair."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    offs = np.cumsum(lens) - lens
    return np.repeat(starts - offs, lens) + np.arange(total)


def forward(indptr: np.ndarray, indices: np.ndarray
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Forward CSR: each vertex's neighbours of larger id, ascending."""
    n = indptr.shape[0] - 1
    row = np.repeat(np.arange(n), np.diff(indptr))
    keep = indices > row
    fptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(row[keep], minlength=n), out=fptr[1:])
    return fptr, indices[keep]


class CliqueCounter:
    """Per-start-vertex counts of triangles (k=3) and 4-cliques (k=4),
    each owned by its smallest vertex."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.n = indptr.shape[0] - 1
        self.fptr, self.fidx = forward(indptr, indices)
        self.fdeg = np.diff(self.fptr)
        row = np.repeat(np.arange(self.n), self.fdeg)
        self.fkeys = row * self.n + self.fidx      # ascending

    def _has(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        key = a * self.n + b
        pos = np.searchsorted(self.fkeys, key)
        return self.fkeys[np.minimum(pos, self.fkeys.shape[0] - 1)] == key

    def _expand(self, owner: np.ndarray, *cols: np.ndarray
                ) -> Tuple[np.ndarray, ...]:
        """Extend partial matches by the forward neighbours of their last
        column: returns (owner, cols..., new)."""
        last = cols[-1] if cols else owner
        ln = self.fdeg[last]
        new = self.fidx[_ranges(self.fptr[last], ln)]
        return (np.repeat(owner, ln),) + tuple(
            np.repeat(c, ln) for c in cols) + (new,)

    def counts(self, starts: np.ndarray, k: int) -> np.ndarray:
        """``int64[len(starts)]``: k-cliques owned by each start vertex
        (negative ids, task padding, count 0)."""
        if k not in (3, 4):
            raise ValueError(f"k={k}: only triangles and 4-cliques")
        starts = np.asarray(starts, np.int64)
        out = np.zeros(starts.shape[0], np.int64)
        live = np.flatnonzero(starts >= 0)
        # blocks of start vertices whose wedges fit BLOCK_WEDGES
        wedges = np.zeros(live.shape[0], np.int64)
        if live.size:
            v = starts[live]
            ln = self.fdeg[v]
            a = self.fidx[_ranges(self.fptr[v], ln)]
            np.add.at(wedges, np.repeat(np.arange(live.size), ln),
                      self.fdeg[a])
        edges = np.searchsorted(np.cumsum(wedges),
                                np.arange(BLOCK_WEDGES, int(wedges.sum())
                                          + BLOCK_WEDGES, BLOCK_WEDGES),
                                side="right")
        lo = 0
        for hi in list(edges) + [live.shape[0]]:
            hi = max(int(hi), lo + 1)
            if lo >= live.shape[0]:
                break
            sel = live[lo:hi]
            out[sel] = self._count_block(starts[sel], k)
            lo = hi
        return out

    def _count_block(self, v: np.ndarray, k: int) -> np.ndarray:
        own, vv, a = self._expand(np.arange(v.shape[0]), v)
        own, vv, a, b = self._expand(own, vv, a)
        hit = self._has(vv, b)                      # triangle (v, a, b)
        own, vv, a, b = own[hit], vv[hit], a[hit], b[hit]
        if k == 4:
            own, vv, a, b, c = self._expand(own, vv, a, b)
            hit = self._has(vv, c) & self._has(a, c)
            own = own[hit]
        return np.bincount(own, minlength=v.shape[0])


def _cycle_key(n: int, x: int, y: int, z: int) -> int:
    """Key of the directed 3-cycle x -> y -> z -> x, smallest vertex
    first."""
    if x < y and x < z:
        f = (x, y, z)
    elif y < z:
        f = (y, z, x)
    else:
        f = (z, x, y)
    return (f[0] * n + f[1]) * n + f[2]


def q1p_deltas(n: int, g0_src: np.ndarray, g0_dst: np.ndarray,
               batches: List[Tuple[np.ndarray, np.ndarray, np.ndarray]]
               ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """ΔR+ and ΔR- keys (sorted ``int64``) of ``q1'`` for each batch
    ``(ops, src, dst)`` in turn, starting from the graph ``g0``."""
    out_: List[Set[int]] = [set() for _ in range(n)]
    in_: List[Set[int]] = [set() for _ in range(n)]
    for a, b in zip(g0_src.tolist(), g0_dst.tolist()):
        out_[a].add(b)
        in_[b].add(a)
    for ops, src, dst in batches:
        upd = list(zip(ops.tolist(), src.tolist(), dst.tolist()))
        minus: Set[int] = set()
        for op, x, y in upd:                 # before the batch
            if op < 0:
                for z in out_[y] & in_[x]:
                    minus.add(_cycle_key(n, x, y, z))
        for op, x, y in upd:
            if op > 0:
                out_[x].add(y)
                in_[y].add(x)
            else:
                out_[x].discard(y)
                in_[y].discard(x)
        plus: Set[int] = set()
        for op, x, y in upd:                 # after the batch
            if op > 0:
                for z in out_[y] & in_[x]:
                    plus.add(_cycle_key(n, x, y, z))
        yield (np.sort(np.fromiter(plus, np.int64, len(plus))),
               np.sort(np.fromiter(minus, np.int64, len(minus))))
