"""Host spans, the profiler capture, and the reduction from trace to numbers.

The reduction works on a plain record of the trace,
``{"device": {plane: [[name, start_ns, dur_ns], ...]}, "host": [[name,
start_ns, dur_ns], ...]}``, which :func:`load_trace` reads out of the
profiler's ``.xplane.pb`` and which the tests keep a small recorded copy
of. Device events are the ops of each device plane's ``XLA Ops`` line;
host events are the benchmark's own spans (``bench.*``), which
``jax.profiler.TraceAnnotation`` writes into the same trace, on the same
clock.
"""

from __future__ import annotations

import contextlib
import time
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "bench."
DEVICE_LINE = "XLA Ops"
Event = Tuple[str, int, int]


class Spans:
    """Spans around the calls into each layer, from the benchmark's files.

    Each span is kept in memory as ``(name, start_s, end_s)`` on the
    host's ``perf_counter`` clock (about a microsecond a span). With
    ``annotate`` (traced runs) it is also written into the profiler's
    trace, and callers may wait for the device inside a span.
    """

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


@contextlib.contextmanager
def capture(logdir: Optional[Path]) -> Iterator[Callable[[], None]]:
    """The JAX profiler over the ``with`` body; off when ``logdir`` is
    None. Yields a function that stops the trace early (a cell that
    traces only the start of its window); it stops at the end anyway."""
    if logdir is None:
        yield lambda: None
        return
    import jax
    # the Python tracer would time every interpreted call of the host
    # path and slow it several times over; host level 1 keeps the
    # benchmark's spans and the runtime's main events, and the device
    # trace is unaffected
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    running = [True]

    def stop() -> None:
        if running[0]:
            running[0] = False
            jax.profiler.stop_trace()
    try:
        yield stop
    finally:
        stop()


def load_trace(logdir: Path) -> Dict[str, object]:
    """The plain record of the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(str(files[-1]))
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                   for line in plane.lines if line.name == DEVICE_LINE
                   for e in line.events]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                        for line in plane.lines for e in line.events
                        if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint, ascending union of ``[start, end)`` rows."""
    if iv.shape[0] == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    end = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > end[:-1]]
    starts = iv[new, 0]
    ends = end[np.r_[np.flatnonzero(new)[1:] - 1, iv.shape[0] - 1]]
    return np.stack([starts, ends], axis=1)


def _clip(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def window_bounds(trace: Dict[str, object], span: str = "window"
                  ) -> Tuple[int, int]:
    """Start and end (ns, trace clock) of the host span ``bench.<span>``."""
    for name, s, d in trace["host"]:
        if name == SPAN_PREFIX + span:
            return s, s + d
    raise KeyError(f"no host span {SPAN_PREFIX + span} in the trace")


def reduce_trace(trace: Dict[str, object], lo: int, hi: int,
                 top: int = 10) -> Dict[str, object]:
    """Device busy time and what filled it, over ``[lo, hi)`` ns.

    ``busy_s``: the union of op intervals on each device, averaged over
    the devices that ran anything; ``window_s``: ``hi - lo``;
    ``op_s``: device seconds per op name (summed over devices, clipped to
    the window); ``chips``: the devices that ran an op inside the window;
    ``device_ops``: the ``top`` names by time; ``idle_gaps``:
    the ``top`` longest gaps of device 0, each named by the innermost
    benchmark span that covers its midpoint (``"none"`` where no span
    does).
    """
    busy = []
    chips = 0
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for k, plane in enumerate(sorted(trace["device"])):
        evs = trace["device"][plane]
        if not evs:
            continue
        iv = np.array([[s, s + d] for _, s, d in evs], np.int64)
        for (name, _, _), (a, b) in zip(evs, np.clip(iv, lo, hi)):
            if b > a:
                op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
        u = _clip(_union(iv), lo, hi)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        chips += u.shape[0] > 0
        if k == 0:
            edges = np.r_[lo, u.ravel(), hi].reshape(-1, 2)
            width = edges[:, 1] - edges[:, 0]
            for i in np.argsort(-width, kind="stable")[:top]:
                if width[i] > 0:
                    gaps.append((_label(trace["host"],
                                        int(edges[i].sum() // 2)),
                                 float(width[i]) * 1e-9))
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {"busy_s": float(np.mean(busy)) if busy else 0.0,
            "window_s": (hi - lo) * 1e-9, "op_s": op_s, "chips": chips,
            "device_ops": [[n.split("(", 1)[0], s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def _label(host: Sequence[Event], t: int) -> str:
    best, width = "none", None
    for name, s, d in host:
        if s <= t < s + d and name != SPAN_PREFIX + "window" and (
                width is None or d < width):
            best, width = name[len(SPAN_PREFIX):], d
    return best


def op_name(event: str) -> str:
    """The op's own name out of an ``XLA Ops`` event, which is the HLO
    instruction's text: ``%gather_intersect_pallas.1 = s32[...] ...`` gives
    ``gather_intersect_pallas.1``."""
    return event.split(" = ", 1)[0].lstrip("%")


def kernel_seconds(summary: Dict[str, object], patterns: Sequence[str]
                   ) -> float:
    """Device seconds of the ops whose own name contains any of
    ``patterns`` (an op's operands are named in its text too)."""
    return sum(s for n, s in summary["op_s"].items()
               if any(p in op_name(n) for p in patterns))


def kernel_seconds_per_chip(summary: Dict[str, object],
                            patterns: Sequence[str]) -> float:
    """:func:`kernel_seconds` per chip: summed over the device planes, over
    the planes that ran anything in the window (one plane: the same
    number). A share of the window taken from it cannot pass 100%."""
    chips = summary["chips"]
    return kernel_seconds(summary, patterns) / chips if chips else 0.0
