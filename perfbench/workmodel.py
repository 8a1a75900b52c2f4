"""The least work a layer has to do, computed from the data, never from
padded shapes.

``triangle_int_bytes``: the triangle plan intersects, for every start
vertex ``v`` of the tasks run and every neighbour ``a > v`` (its level-1
frontier), the adjacency rows of ``v`` and ``a``. Reading both at their
real lengths costs ``4 * (deg(v) + deg(a))`` bytes of int32; the sum over
all such pairs is the least traffic of the intersections, whatever
layout, bucketing or probe a later version uses.
"""

from __future__ import annotations

import numpy as np


def triangle_int_bytes(indptr: np.ndarray, indices: np.ndarray,
                       tasks: np.ndarray) -> int:
    """Least bytes the triangle plan's intersections read for ``tasks``
    (start ids, ``-1`` for padding)."""
    deg = np.diff(indptr)
    v = np.asarray(tasks).ravel()
    v = v[v >= 0]
    lens = deg[v]
    offs = np.cumsum(lens) - lens
    pos = np.repeat(indptr[v] - offs, lens) + np.arange(int(lens.sum()))
    a = indices[pos]
    fwd = a > np.repeat(v, lens)
    return 4 * int((np.repeat(deg[v], lens)[fwd] + deg[a[fwd]]).sum())
