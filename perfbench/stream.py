"""Stream cells: a standing query over an update stream, open loop.

Set-up generates the initial graph and the whole update stream from the
seed, pins the snapshot row widths from it, and runs the first
``warm_steps`` batches (compiling every shape the window uses). The
window then offers one batch every ``period_ms`` on a fixed schedule,
whether or not the previous step has finished, through ``run_timestep``
with the ``sbenu-jax`` engine. A step's latency runs from its due time to
ΔR+ and ΔR- on the host, so a stall also delays the steps queued behind
it. Every step that ran is then checked against the plain reference.
"""

from __future__ import annotations

import contextlib
import gc
import math
import resource
import time
from typing import Dict, List, Tuple

import numpy as np

import graphgen
import reference
from cell import CellOutput
from tracing import Spans


class GcLog:
    """Every collection of the interpreter's cyclic GC, as ``(generation,
    start_s, end_s)`` on the ``perf_counter`` clock."""

    def __init__(self):
        self.events: List[Tuple[int, float, float]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.events.append((info["generation"], self._t0,
                                time.perf_counter()))

    def close(self) -> None:
        gc.callbacks.remove(self._cb)

    def within(self, a: float, b: float) -> Tuple[float, int]:
        """GC seconds inside ``[a, b]`` and the oldest generation
        collected there (-1 for none)."""
        s, gen = 0.0, -1
        for g, x, y in self.events:
            if y > a and x < b:
                s += min(y, b) - max(x, a)
                gen = max(gen, g)
        return s, gen


def _keys(matches, n: int) -> np.ndarray:
    """Sorted int64 keys of a set of 3-tuples."""
    if not matches:
        return np.zeros(0, np.int64)
    m = np.asarray(list(matches), np.int64)
    return np.sort((m[:, 0] * n + m[:, 1]) * n + m[:, 2])


def _timed_classes(spans: Spans):
    from repro.core.executor import SBenuJaxBackend
    from repro.graph.dynamic import SnapshotStore

    class TimedStore(SnapshotStore):
        """The program's snapshot store with spans around its host
        steps."""

        def begin_step(self, batch):
            with spans.span("begin_step"):
                super().begin_step(batch)

        def end_step(self):
            with spans.span("end_step"):
                super().end_step()

    class TimedBackend(SBenuJaxBackend):
        """``sbenu-jax`` with spans: ``snapshot`` around the per-step
        prepare (the snapshot advance; waited for on the device in traced
        runs), ``chunk`` around each delta-enumeration dispatch."""

        def prepare(self, plans, source, config):
            with spans.span("snapshot"):
                super().prepare(plans, source, config)
                if spans.annotate:
                    import jax
                    jax.block_until_ready((self.snap.cur_out,
                                           self.snap.cur_in))

        def run_chunk(self, ids, valid, universe_chunk, caps):
            with spans.span("chunk"):
                return super().run_chunk(ids, valid, universe_chunk, caps)

    return TimedStore, TimedBackend


def run_cell(cfg: Dict, mix: Dict, seed: int, seconds: float,
             spans: Spans, capture) -> CellOutput:
    from repro.core.estimate import GraphStats
    from repro.core.pattern import get_pattern
    from repro.core.sbenu import generate_best_sbenu_plans, run_timestep
    from repro.graph.storage import DiGraph

    log: Dict[str, object] = {}
    scfg = cfg
    n, batch = scfg["n_vertices"], scfg["batch"]
    period = mix["period_ms"] / 1e3
    n_window = math.ceil(seconds / period)
    warm = mix["warm_steps"]
    t = time.perf_counter()
    st = graphgen.edge_stream(seed, n, scfg["m0"], warm + n_window, batch,
                              scfg["delete_share"],
                              scfg["gamma"], scfg["hub_degree"])
    log["gen_s"] = time.perf_counter() - t
    width, dwidth = scfg["row_width"], scfg["delta_row_width"]
    floors = (max(st.d_out, st.d_in), max(st.dd_out, st.dd_in))
    if floors[0] > width or floors[1] > dwidth:
        raise ValueError(f"stream needs widths {floors}, above the pinned "
                         f"({width}, {dwidth})")
    log.update(n=n, m0=scfg["m0"], batch=batch, period_ms=mix["period_ms"],
               steps=warm + n_window, warm_steps=warm, width_floors=floors,
               widths=(width, dwidth))
    pattern = get_pattern(mix["pattern"])
    plans = generate_best_sbenu_plans(
        pattern, GraphStats(n, scfg["m0"], delta_edges=batch))
    TimedStore, TimedBackend = _timed_classes(spans)
    t = time.perf_counter()
    store = TimedStore(DiGraph.from_edges(
        n, zip(st.g0_src.tolist(), st.g0_dst.tolist())))
    backend = TimedBackend(pattern, collect="matches", d_min=width,
                           delta_d_min=dwidth)
    log["load_s"] = time.perf_counter() - t
    signs = np.array(["-", "", "+"])

    def step(k: int):
        upd = list(zip(signs[st.ops[k] + 1].tolist(), st.src[k].tolist(),
                       st.dst[k].tolist()))
        plus, minus, _ = run_timestep(pattern, plans, store, upd,
                                      engine="sbenu-jax", collect="matches",
                                      chunk=mix["chunk"], backend=backend)
        return _keys(plus, n), _keys(minus, n)

    results: List[Tuple[np.ndarray, np.ndarray]] = []
    t = time.perf_counter()
    warm_ms = []
    for k in range(warm):
        s0 = time.perf_counter()
        results.append(step(k))
        warm_ms.append((time.perf_counter() - s0) * 1e3)
    log["warm_s"] = time.perf_counter() - t
    log["warm_step_ms"] = [round(x, 3) for x in warm_ms]
    mirror = store._mirrors[0]
    rebuilds0 = mirror.rebuilds

    rec = np.zeros((n_window, 5))          # due, start, end, cpu0, cpu1
    faults = np.zeros(n_window, np.int64)  # minor page faults per step
    rebuilt = np.zeros(n_window, np.int64)
    failed = 0
    gcl = GcLog()
    # a traced run traces the window's first trace_seconds only: the
    # profiler's record of a whole 51-s window outgrew a 40 GiB host
    n_traced = math.ceil(mix.get("trace_seconds", seconds) / period)
    with capture() as stop_trace, contextlib.ExitStack() as traced:
        t0 = time.perf_counter() + 0.05
        traced.enter_context(spans.span("window"))
        for j in range(n_window):
            if j == n_traced:
                traced.close()
                stop_trace()
            due = t0 + j * period
            now = time.perf_counter()
            if now < due:
                time.sleep(due - now)
            r0 = mirror.rebuilds
            f0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
            rec[j, 0], rec[j, 1] = due, time.perf_counter()
            rec[j, 3] = time.process_time()
            try:
                with spans.span("step"):
                    results.append(step(warm + j))
            except Exception as e:                 # noqa: BLE001
                log.setdefault("step_errors", []).append(repr(e)[:200])
                results.append(None)
                failed += 1
            rec[j, 2], rec[j, 4] = time.perf_counter(), time.process_time()
            rebuilt[j] = mirror.rebuilds - r0
            faults[j] = (resource.getrusage(resource.RUSAGE_SELF)
                         .ru_minflt - f0)
    gcl.close()
    lat_ms = (rec[:, 2] - rec[:, 0]) * 1e3
    service_ms = (rec[:, 2] - rec[:, 1]) * 1e3
    # how late the generator emitted a batch while the system was idle
    idle = np.r_[True, rec[:-1, 2] <= rec[1:, 0]]
    gen_late_ms = (rec[idle, 1] - rec[idle, 0]) * 1e3
    done = rec[:, 2] <= t0 + seconds
    w = int(np.argmax(lat_ms))
    gc_s, gc_gen = gcl.within(rec[w, 1], rec[w, 2])
    gc_window = gcl.within(t0, rec[-1, 2])
    log.update(
        window_steps=n_window, window_s=float(rec[-1, 2] - t0),
        step_ms_p50=float(np.percentile(lat_ms, 50)),
        step_ms_p95=float(np.percentile(lat_ms, 95)),
        step_ms_max=float(lat_ms.max()),
        last_step_ms=float(lat_ms[-1]),
        service_ms_p50=float(np.percentile(service_ms, 50)),
        gen_late_ms_max=float(gen_late_ms.max()) if gen_late_ms.size else 0.0,
        rebuilds_in_window=int(mirror.rebuilds - rebuilds0),
        gc_in_window_s=gc_window[0],
        gc_gen2_in_window=sum(1 for g, a, b in gcl.events
                              if g == 2 and a >= t0),
        worst_step=dict(index=warm + w, latency_ms=float(lat_ms[w]),
                        start_late_ms=float((rec[w, 1] - rec[w, 0]) * 1e3),
                        service_ms=float(service_ms[w]),
                        cpu_ms=float((rec[w, 4] - rec[w, 3]) * 1e3),
                        gc_ms=gc_s * 1e3, gc_gen=gc_gen,
                        rebuilds=int(rebuilt[w])))
    enum_ms = _per_step(spans, "chunk", rec)
    log["phase_ms_p50"] = {
        k: float(np.median(_per_step(spans, k, rec)))
        for k in ("begin_step", "snapshot", "chunk", "end_step")}
    log["minor_faults_per_step"] = [int(np.median(faults)),
                                    int(faults.max())]
    return CellOutput(
        attempted=n_window + warm, failed=failed,
        e2e={"stream_step_p50_ms": float(np.percentile(lat_ms, 50)),
             "stream_updates_per_s": float(done.sum() * batch / seconds)},
        layer={"spans": spans, "window_s": float(rec[-1, 2] - t0),
               "delta_enum_ms": enum_ms},
        log=log,
        check=lambda: _check(st, results))


def _per_step(spans: Spans, name: str, rec: np.ndarray) -> List[float]:
    """Milliseconds of span ``name`` inside each window step."""
    out = np.zeros(rec.shape[0])
    ends = rec[:, 2]
    for nm, a, b in spans.records:
        if nm == name:
            j = int(np.searchsorted(ends, b))
            if j < out.shape[0] and a >= rec[j, 1]:
                out[j] += (b - a) * 1e3
    return out.tolist()


def _check(st, results) -> Dict[str, tuple]:
    """Each step's ΔR+ and ΔR- against the reference's: steps that differ
    (limit 0) and match tuples missing or extra over all steps."""
    ref = reference.q1p_deltas(
        st.n, st.g0_src, st.g0_dst,
        [(st.ops[k], st.src[k], st.dst[k]) for k in range(len(results))])
    wrong_steps = wrong_tuples = 0
    for got, want in zip(results, ref):
        if got is None:
            wrong_steps += 1
            wrong_tuples += want[0].size + want[1].size
            continue
        bad = sum(np.setxor1d(g, w).size for g, w in zip(got, want))
        wrong_steps += bad > 0
        wrong_tuples += bad
    return {"steps_wrong": (wrong_steps, 0),
            "delta_tuples_wrong": (wrong_tuples, 0)}
