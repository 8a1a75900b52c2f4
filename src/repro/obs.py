"""Spans and counters the engine records about itself.

A span times one stretch of host work at a layer boundary (a time step,
the snapshot advance, a chunk's dispatch); a counter adds up what that
work moved (bytes sent to the device, chunks split, programs built).
Both are always on and cost about a microsecond each, so an untraced run
can still say where a slow step spent its time.

* :func:`span` records ``(name, span_id, parent_id, key, t0_ns, t1_ns)``
  into a bounded ring (the newest :data:`RING` spans) on the
  ``time.perf_counter_ns`` clock. The parent is the span open on this
  thread. ``key`` names the request a span serves (the time step ``t`` of
  a stream, one ``drive`` call of a census); a span opened without one
  inherits its parent's. Each span is also a
  ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so under the
  profiler it lands on the host plane of the device trace's clock.
* :func:`count` adds to a counter, kept in total and per key (the key of
  the span open on this thread), so a per-step counter can be read for
  every step.

A span ends where its body ends: nothing here waits for the device.

    >>> from repro import obs
    >>> obs.reset()
    >>> with obs.span("timestep", key=7):
    ...     with obs.span("snapshot.place"):
    ...         obs.count("snapshot.h2d_bytes", 64)
    >>> [(r.name, r.key) for r in obs.records()]
    [('snapshot.place', 7), ('timestep', 7)]
    >>> obs.counters()["snapshot.h2d_bytes"], obs.counters(key=7)
    (64, {'snapshot.h2d_bytes': 64})
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, NamedTuple, Optional

#: spans kept in memory (the oldest are dropped first)
RING = 1 << 16
#: request keys whose counters are kept (the oldest are dropped first)
KEYS = 1 << 12
PREFIX = "repro."


class Record(NamedTuple):
    name: str
    span_id: int
    parent_id: Optional[int]
    key: Optional[Hashable]
    t0_ns: int
    t1_ns: int


_records: collections.deque = collections.deque(maxlen=RING)
_totals: collections.Counter = collections.Counter()
_by_key: "collections.OrderedDict[Hashable, collections.Counter]" = \
    collections.OrderedDict()
_ids = itertools.count(1)
_local = threading.local()
_now = time.perf_counter_ns
_annotation: Any = None


def _stack() -> List["span"]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """``with span(name, key=None):`` times its body (see the module
    docstring)."""

    __slots__ = ("name", "key", "span_id", "parent_id", "t0", "_ann")

    def __init__(self, name: str, key: Optional[Hashable] = None):
        self.name, self.key = name, key

    def __enter__(self) -> "span":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        st = _stack()
        if st:
            parent = st[-1]
            self.parent_id = parent.span_id
            if self.key is None:
                self.key = parent.key
        else:
            self.parent_id = None
        self.span_id = next(_ids)
        # an annotation records nothing while no profiler runs: skip it
        self._ann = None
        if _annotation.is_enabled():
            self._ann = _annotation(PREFIX + self.name)
            self._ann.__enter__()
        st.append(self)
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _now()
        _local.stack.pop()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _records.append((self.name, self.span_id, self.parent_id, self.key,
                         self.t0, t1))


def current_key() -> Optional[Hashable]:
    """The key of the span open on this thread (None outside spans)."""
    st = _stack()
    return st[-1].key if st else None


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``, in total and under the current key."""
    _totals[name] += n
    key = current_key()
    if key is None:
        return
    per = _by_key.get(key)
    if per is None:
        per = _by_key[key] = collections.Counter()
        if len(_by_key) > KEYS:
            _by_key.popitem(last=False)
    per[name] += n


def records() -> List[Record]:
    """The spans in the ring, in the order they ended."""
    return [Record(*r) for r in _records]


def counters(key: Optional[Hashable] = None) -> Dict[str, int]:
    """Counter totals, or those counted under ``key``."""
    if key is None:
        return dict(_totals)
    return dict(_by_key.get(key, {}))


def reset() -> None:
    """Forget every span and counter."""
    _records.clear()
    _totals.clear()
    _by_key.clear()


class build_on_first_call:
    """``fn`` (a freshly jitted program) whose first call - tracing,
    compiling and the first dispatch - runs in a ``jit.build`` span and
    counts ``jit.builds``; every other attribute is ``fn``'s."""

    def __init__(self, fn: Callable):
        self.fn, self.built = fn, False

    def __call__(self, *args):
        if self.built:
            return self.fn(*args)
        self.built = True
        with span("jit.build"):
            count("jit.builds")
            return self.fn(*args)

    def __getattr__(self, name: str):
        return getattr(self.fn, name)


def self_times(recs: Optional[List[Record]] = None) -> Dict[str, float]:
    """Seconds per span name of its own time: each span's duration less
    that of its child spans (those in ``recs``)."""
    recs = records() if recs is None else recs
    own: Dict[int, int] = {r.span_id: r.t1_ns - r.t0_ns for r in recs}
    for r in recs:
        if r.parent_id in own:
            own[r.parent_id] -= r.t1_ns - r.t0_ns
    out: Dict[str, float] = collections.defaultdict(float)
    for r in recs:
        out[r.name] += own[r.span_id] * 1e-9
    return dict(out)
