"""The program's own record of a traced run, for per-layer metrics.

Two sources, both written by the program (``src/repro``), never by the
benchmark's files:

* the profiler's ``.xplane.pb`` of the run (the newest under the run's
  ``.bench_cache/trace/<cell>/``): every device op with the layer scope
  it ran under (``enu``, ``int``, ``dbq``, ``derive``: the program's
  ``jax.named_scope``s, carried in the op's metadata), or failing that
  the jitted program it belongs to (``derive`` is a program of its own);
  and the host spans ``repro.*`` of ``repro.obs``, with the benchmark's
  ``bench.window``, on the same clock;
* ``repro.obs`` in this process: the in-memory spans and per-step
  counters, on the ``perf_counter`` clock of the cell's window.

Every function returns None where its input is missing (no trace, a
program that records no spans or scopes), and never raises for that.
"""

from __future__ import annotations

import mmap
import re
import statistics
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from tracing import _union

SCOPES = ("enu", "int", "dbq", "derive")
HOST_PREFIX = "repro."
WINDOW = "bench.window"
DEVICE_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"

_cache: Dict[Tuple[str, float], Dict] = {}


# ------------------------------------------------------------- xplane


def _varint(buf, i: int) -> Tuple[int, int]:
    x = s = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << s
        if b < 0x80:
            return x, i
        s += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message in ``buf[i:end]``:
    an int for varints, a ``(start, end)`` span for length-delimited
    fields, raw bytes for fixed-width ones."""
    while i < end:
        key, i = _varint(buf, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield f, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _op_metadata(path: Path) -> Dict[str, Dict[str, Dict[str, str]]]:
    """String stats of each device plane's event metadata, by event name:
    ``{plane: {event name: {stat name: value}}}``. Reads the XSpace
    proto's plane names and metadata maps only; lines are skipped."""
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    with open(path, "rb") as fh, \
            mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for f, plane in _fields(buf, 0, len(buf)):
            if f != 1:                       # XSpace.planes
                continue
            name, events, stat_names = "", [], {}
            for pf, v in _fields(buf, *plane):
                if pf == 2:                  # XPlane.name
                    name = _text(buf, v)
                    if not name.startswith("/device:"):
                        break
                elif pf == 4:                # event_metadata map entry
                    events.append(v)
                elif pf == 5:                # stat_metadata map entry
                    for ef, ev in _fields(buf, *v):
                        if ef == 2:
                            sid = sname = None
                            for sf, sv in _fields(buf, *ev):
                                if sf == 1:
                                    sid = sv
                                elif sf == 2:
                                    sname = _text(buf, sv)
                            stat_names[sid] = sname
            if not name.startswith("/device:"):
                continue
            meta: Dict[str, Dict[str, str]] = {}
            for entry in events:
                for ef, ev in _fields(buf, *entry):
                    if ef != 2:              # the XEventMetadata value
                        continue
                    names, stats = [], []
                    for mf, mv in _fields(buf, *ev):
                        if mf in (2, 4):     # name, display_name
                            names.append(_text(buf, mv))
                        elif mf == 5:        # stats
                            stats.append(mv)
                    got = {}
                    for st in stats:
                        sid = val = None
                        for sf, sv in _fields(buf, *st):
                            if sf == 1:
                                sid = sv
                            elif sf == 5:    # str_value
                                val = _text(buf, sv)
                            elif sf == 7:    # ref_value
                                val = ("ref", sv)
                        if isinstance(val, tuple):
                            val = stat_names.get(val[1])
                        if sid is not None and val is not None:
                            got[stat_names.get(sid, str(sid))] = val
                    for nm in names:
                        if nm:
                            meta[nm] = got
            out[name] = meta
    return out


def scope_of(stats: Dict[str, str], module: str = "") -> Optional[str]:
    """The layer scope of one device op: the innermost of :data:`SCOPES`
    in the op's scope path (an op name such as ``jit(run)/enu/scatter``),
    else a scope that names its program (``jit_derive``), else None."""
    for key in ("tf_op", "op_name", "long_name"):
        hit = [p for p in str(stats.get(key, "")).split("/") if p in SCOPES]
        if hit:
            return hit[-1]
    words = re.split(r"[^a-z0-9]+", module.lower())
    hit = [w for w in words if w in SCOPES]
    return hit[-1] if hit else None


def load(trace_dir: Path) -> Optional[Dict]:
    """The newest trace under ``trace_dir`` as a plain record (read once):

    ``{"device": {plane: [[op, start_ns, dur_ns, scope], ...]},
    "host": [[span, start_ns, dur_ns], ...]}``, with the host spans
    ``repro.*`` and ``bench.*`` only. None without a trace."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    key = (str(files[-1]), files[-1].stat().st_mtime)
    if key not in _cache:
        _cache.clear()
        _cache[key] = _read(files[-1])
    return _cache[key]


def _read(path: Path) -> Dict:
    from jax.profiler import ProfileData
    meta = _op_metadata(path)
    pd = ProfileData.from_file(str(path))
    device: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if DEVICE_LINE not in lines:
                continue
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(", 1)[0])
                          for e in lines.get(MODULE_LINE, ()).events
                          ) if MODULE_LINE in lines else []
            starts = np.array([m[0] for m in mods], np.float64)
            pmeta = meta.get(plane.name, {})
            evs = []
            for e in lines[DEVICE_LINE].events:
                j = int(np.searchsorted(starts, e.start_ns, "right")) - 1
                module = (mods[j][2] if j >= 0 and e.start_ns < mods[j][1]
                          else "")
                evs.append([e.name, int(e.start_ns), int(e.duration_ns),
                            scope_of(pmeta.get(e.name, {}), module)])
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                        for ln in plane.lines for e in ln.events
                        if e.name.startswith((HOST_PREFIX, "bench.")))
    return {"device": device, "host": host}


# ------------------------------------------------------------- trace reads


def window(rec: Dict) -> Optional[Tuple[int, int]]:
    """``[start, end)`` ns of the benchmark's window span."""
    for name, s, d in rec["host"]:
        if name == WINDOW:
            return s, s + d
    return None


def scope_seconds(rec: Dict) -> Dict[Optional[str], float]:
    """Device seconds per scope (None: ops with no scope) inside the
    window, summed over the device planes."""
    lo, hi = window(rec)
    out: Dict[Optional[str], float] = {}
    for evs in rec["device"].values():
        for _, s, d, scope in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out[scope] = out.get(scope, 0.0) + (b - a) * 1e-9
    return out


def scope_seconds_per_chip(rec: Dict) -> Dict[Optional[str], float]:
    """:func:`scope_seconds` per chip: summed over the device planes, over
    the planes that ran anything in the window (one plane: the same
    numbers). A share of the window taken from it cannot pass 100%."""
    lo, hi = window(rec)
    chips = sum(any(min(s + d, hi) > max(s, lo) for _, s, d, _ in evs)
                for evs in rec["device"].values())
    return {k: v / chips for k, v in scope_seconds(rec).items()} \
        if chips else {}


def host_spans(rec: Dict, name: str) -> List[Tuple[int, int]]:
    """``[start, end)`` ns of host span ``repro.<name>`` that start in the
    window."""
    lo, hi = window(rec)
    return [(s, s + d) for n, s, d in rec["host"]
            if n == HOST_PREFIX + name and lo <= s < hi]


def busy_union(rec: Dict, plane: Optional[str] = None) -> np.ndarray:
    """Disjoint ascending ``[start, end)`` rows of device activity on one
    plane (the first by name when None)."""
    if not rec["device"]:
        return np.zeros((0, 2), np.int64)
    plane = plane or sorted(rec["device"])[0]
    return _union(np.array([[s, s + d] for _, s, d, _ in
                            rec["device"][plane]], np.int64).reshape(-1, 2))


def covered(union: np.ndarray, a: int, b: int) -> int:
    """ns of ``[a, b)`` that the disjoint rows of ``union`` cover."""
    if union.shape[0] == 0 or b <= a:
        return 0
    s = np.clip(union[:, 0], a, b)
    e = np.clip(union[:, 1], a, b)
    return int((e - s).sum())


def trace_record(ctx: Dict) -> Optional[Dict]:
    """This run's trace record (the newest under ``ctx["trace_dir"]``,
    where the run put it), or None (an untraced run, no window)."""
    if not ctx.get("trace") or not ctx.get("trace_dir"):
        return None
    rec = load(ctx["trace_dir"])
    if rec is None or window(rec) is None:
        return None
    return rec


def scope_share(ctx: Dict, scope: str) -> Optional[float]:
    """Device time per chip under ``scope`` over the window (%)."""
    rec = trace_record(ctx)
    if rec is None:
        return None
    sec = scope_seconds_per_chip(rec).get(scope)
    lo, hi = window(rec)
    return 100.0 * sec / ((hi - lo) * 1e-9) if sec else None


def scope_ms_per_step(ctx: Dict, scope: str) -> Optional[float]:
    """Device milliseconds per chip under ``scope`` per traced time
    step."""
    rec = trace_record(ctx)
    if rec is None:
        return None
    steps = host_spans(rec, "timestep")
    sec = scope_seconds_per_chip(rec).get(scope)
    return 1e3 * sec / len(steps) if steps and sec else None


# ------------------------------------------------------------- repro.obs


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def window_steps(ctx: Dict) -> Optional[List]:
    """Keys of the time steps whose ``timestep`` span started inside the
    cell's window (``ctx["window"]``, perf_counter seconds)."""
    obs = _obs()
    if obs is None or "window" not in ctx:
        return None
    lo, hi = ctx["window"]
    keys = [r.key for r in obs.records() if r.name == "timestep"
            and lo <= r.t0_ns * 1e-9 <= hi]
    return keys or None


def span_ms_p50(ctx: Dict, name: str) -> Optional[float]:
    """Median over the window's steps of span ``name``'s milliseconds in
    each step (its spans summed)."""
    keys = window_steps(ctx)
    if keys is None:
        return None
    per = dict.fromkeys(keys, 0.0)
    seen = False
    for r in _obs().records():
        if r.name == name and r.key in per:
            per[r.key] += (r.t1_ns - r.t0_ns) * 1e-6
            seen = True
    return statistics.median(per.values()) if seen else None


def counter_p50(ctx: Dict, name: str) -> Optional[float]:
    """Median over the window's steps of counter ``name``."""
    keys = window_steps(ctx)
    if keys is None:
        return None
    obs = _obs()
    vals = [obs.counters(key=k).get(name) for k in keys]
    if all(v is None for v in vals):
        return None
    return float(statistics.median(v or 0 for v in vals))
