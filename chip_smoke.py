#!/usr/bin/env python3
"""Chip smoke run: BENU's enumeration main path, once, on a TPU.

    python3 chip_smoke.py [--seed 0]                 # one chip
    python3 chip_smoke.py --four-chips [--seed 0]    # dist paths, 4 chips

Everything runs in this one process, through the entry points a user
calls (``make_executor(...).run`` and ``run_timestep``), on data made
from ``--seed``:

(a) device check: a TPU must be attached, no ``REPRO_*_IMPL`` /
    ``REPRO_FUSED_FETCH`` override may be set, and both INT kernels
    (``intersect``, ``gather_intersect``) must resolve to compiled Pallas;
(b) static census on ``powerlaw(300_000, 4)`` (1.2M edges, max degree
    2130, padded rows 300,000 x 2176 int32 = 2.6 GB in HBM): ``triangle``
    and ``chordal-square`` through ``jax`` and ``jax-gpu``; the triangle
    count must equal an independent numpy count, and ``jax`` must equal
    ``jax-gpu``. On a 2,000-vertex graph ``ref``, ``jax`` and ``jax-gpu``
    must agree for both patterns;
(c) continuous enumeration: ``q1'`` over a 3-step edge stream (100,000
    vertices, 500,000 initial edges, 10,000 updates per step) with
    ``sbenu-jax``, against the ``sbenu`` interpreter's ΔR± match sets.

``--four-chips`` runs only the paths that exist across chips: ``dist``
(``hot=64``, rebalancing on) on the phase-(b) graph against the
single-chip ``jax`` count and the numpy count, and ``sbenu-dist`` against
``sbenu-jax`` on the phase-(c) stream.

Earlier output lines give each phase's wall and compile seconds (kept
apart), counts, driver chunk accounting, resolved kernel impls,
``peak_bytes_in_use`` and the host's peak RSS. A failed phase or a
mismatch exits non-zero. The last line is the device record,
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import resource
import sys
import time
from pathlib import Path
from typing import Dict, Iterator, List

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: phase (b): the real-size static graph, powerlaw(n, m_per_node)
STATIC_N, STATIC_M = 300_000, 4
#: phase (b): the small graph every engine (``ref`` included) must agree on
SMALL_N = 2_000
STATIC_PATTERNS = ("triangle", "chordal-square")
#: driver config for the real-size runs: start vertices per chunk and
#: per-ENU frontier capacities (rows). The default caps (batch * D / 4 at
#: the first level) would put 139,264 rows x 2176 lanes through every
#: chunk's INT. These cover the largest chunk of the seed-0 graph (level
#: sizes at most 10,108 / 437 for triangle, 10,108 / 2,644 / 5,870 for
#: chordal-square), so each run compiles one chunk program; a chunk that
#: still overflows is split or grown by the driver, so counts stay exact.
STATIC_BATCH = 2048
STATIC_CAPS = {"triangle": (12288, 1024),
               "chordal-square": (12288, 4096, 8192)}
#: phase (c): edge_stream(n, m_init, steps, batch) and the driver chunk
STREAM = dict(n=100_000, m_init=500_000, steps=3, batch=10_000)
STREAM_PATTERN = "q1'"
STREAM_CHUNK = 1024
#: --four-chips: dist's replicated hot rows, per-shard frontier caps
#: (a quarter of STATIC_CAPS, rounded up) and per-peer fetch requests
#: (the top id block owns the high-degree rows most DBQs ask for)
DIST_HOT = 64
DIST_CAPS = (4096, 1024)
DIST_REQ_CAP = 4096

#: compile seconds = lowering to MLIR + the XLA/Mosaic backend compile
#: (tracing is left out: nested jits would count it twice)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer or ran on the wrong device."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def host_peak_rss() -> int:
    """This process's peak resident host memory, bytes (Linux ru_maxrss
    is in KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _count_compile(event: str, secs: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


@contextlib.contextmanager
def timed() -> Iterator[Dict[str, float]]:
    """Wall seconds and the compile seconds spent inside them."""
    rec: Dict[str, float] = {}
    c0, t0 = _compile_s[0], time.perf_counter()
    yield rec
    rec["wall_s"] = time.perf_counter() - t0
    rec["compile_s"] = _compile_s[0] - c0


# --------------------------------------------------------------------------
# (a) device check
# --------------------------------------------------------------------------


def impl_overrides() -> List[str]:
    return sorted(k for k in os.environ
                  if (k.startswith("REPRO_") and k.endswith("_IMPL"))
                  or k == "REPRO_FUSED_FETCH")


def check_device(want_count: int) -> Dict[str, object]:
    """Phase (a): the device record, or SmokeFailure off the chip."""
    over = impl_overrides()
    check(not over, f"kernel overrides set: {over}")
    import jax
    from repro.kernels.dispatch import resolve_impl
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("a", platform=dev["platform"], kind=repr(dev["kind"]),
        count=dev["count"], host_peak_rss=host_peak_rss())
    check(dev["platform"] == "tpu", f"no TPU: JAX found {dev['platform']}")
    check(dev["count"] >= want_count,
          f"need {want_count} chips, found {dev['count']}")
    impls = {op: resolve_impl(op) for op in ("intersect", "gather_intersect")}
    log("a", **{f"impl_{k}": v for k, v in impls.items()})
    check(all(v == "pallas" for v in impls.values()),
          f"INT kernels must resolve to compiled Pallas: {impls}")
    return dev


# --------------------------------------------------------------------------
# oracles
# --------------------------------------------------------------------------


def numpy_triangles(g) -> int:
    """Triangles of ``g`` by closing forward wedges, numpy only.

    Each triangle ``u < v < w`` is the wedge ``(v, w)`` of ``u``'s forward
    list closed by the edge ``v -> w``; forward lists are short because
    the generator's ids ascend with degree.
    """
    deg = np.array([len(a) for a in g.adj], np.int64)
    src = np.repeat(np.arange(g.n, dtype=np.int64), deg)
    dst = np.concatenate(g.adj).astype(np.int64)
    fwd = dst > src
    src, dst = src[fwd], dst[fwd]                   # sorted by (src, dst)
    keys = src * g.n + dst
    row_end = np.searchsorted(src, src, side="right")
    total = 0
    pos = np.arange(src.shape[0])
    for t in range(1, int(np.bincount(src).max(initial=0))):
        p = pos[pos + t < row_end]
        if p.size == 0:
            break
        q = dst[p] * g.n + dst[p + t]
        i = np.minimum(np.searchsorted(keys, q), keys.shape[0] - 1)
        total += int(np.count_nonzero(keys[i] == q))
    return total


# --------------------------------------------------------------------------
# (b) static census
# --------------------------------------------------------------------------


def run_static(engine: str, plan, g, **cfg):
    from repro.core.executor import make_executor
    with timed() as rec:
        st = make_executor(engine).run(plan, g, **cfg)
    return st, rec


def log_stats(phase: str, label: str, st, rec, **extra) -> None:
    log(phase, run=label, count=st.count, wall_s=rec["wall_s"],
        compile_s=rec["compile_s"], chunks_run=st.chunks_run,
        chunks_split=st.chunks_split, chunks_retried=st.chunks_retried,
        peak_bytes_in_use=peak_bytes(), host_peak_rss=host_peak_rss(),
        **extra)


def static_graph(seed: int, phase: str):
    from repro.graph.generate import powerlaw
    with timed() as rec:
        g = powerlaw(STATIC_N, STATIC_M, seed=seed)
        tri = numpy_triangles(g)
    d = int(max(len(a) for a in g.adj))
    log(phase, graph=f"powerlaw({STATIC_N},{STATIC_M})", n=g.n, m=g.m,
        max_degree=d, numpy_triangles=tri, setup_s=rec["wall_s"],
        host_peak_rss=host_peak_rss())
    return g, tri


def phase_static(seed: int) -> Dict[str, int]:
    """Phase (b) at real size; returns the ``jax`` counts by pattern."""
    from repro.core.pattern import get_pattern
    from repro.core.plangen import generate_best_plan
    from repro.kernels.dispatch import resolve_impl
    g, tri = static_graph(seed, "b")
    counts: Dict[str, Dict[str, int]] = {}
    for pname in STATIC_PATTERNS:
        plan = generate_best_plan(get_pattern(pname), g.stats())
        cfg = dict(batch=STATIC_BATCH, caps=STATIC_CAPS[pname])
        for engine in ("jax", "jax-gpu"):
            st, rec = run_static(engine, plan, g, **cfg)
            impls = {"intersect": resolve_impl("intersect")}
            if engine == "jax-gpu":
                check(st.extras["fused_fetch"] is True,
                      "jax-gpu ran without the fused fetch")
                impls["gather_intersect"] = resolve_impl("gather_intersect")
            log_stats("b", f"{engine}/{pname}", st, rec, batch=cfg["batch"],
                      caps=",".join(map(str, cfg["caps"])),
                      impls=",".join(f"{k}:{v}" for k, v in impls.items()))
            counts.setdefault(pname, {})[engine] = st.count
        check(counts[pname]["jax"] == counts[pname]["jax-gpu"],
              f"{pname}: jax != jax-gpu: {counts[pname]}")
    check(counts["triangle"]["jax"] == tri,
          f"triangle: jax {counts['triangle']['jax']} != numpy {tri}")
    log("b", check="triangle == numpy and jax == jax-gpu", ok=True)
    return {p: c["jax"] for p, c in counts.items()}


def phase_small(seed: int, n: int = SMALL_N) -> None:
    """Phase (b), small graph: ``ref == jax == jax-gpu`` on the chip."""
    from repro.core.pattern import get_pattern
    from repro.core.plangen import generate_best_plan
    from repro.graph.generate import powerlaw
    g = powerlaw(n, STATIC_M, seed=seed)
    tri = numpy_triangles(g)
    for pname in STATIC_PATTERNS:
        plan = generate_best_plan(get_pattern(pname), g.stats())
        got = {}
        for engine in ("ref", "jax", "jax-gpu"):
            st, rec = run_static(engine, plan, g, batch=256)
            log_stats("b-small", f"{engine}/{pname}", st, rec, n=n)
            got[engine] = st.count
        check(len(set(got.values())) == 1, f"{pname} n={n}: {got}")
        if pname == "triangle":
            check(got["ref"] == tri, f"triangle n={n}: {got} vs {tri}")
    log("b-small", check="ref == jax == jax-gpu", ok=True)


# --------------------------------------------------------------------------
# (c) continuous enumeration
# --------------------------------------------------------------------------


def make_stream(seed: int, phase: str):
    from repro.core.estimate import GraphStats
    from repro.core.pattern import get_pattern
    from repro.core.sbenu import generate_best_sbenu_plans
    from repro.graph.dynamic import stream_width_floors
    from repro.graph.generate import edge_stream
    with timed() as rec:
        g0, batches = edge_stream(seed=seed, **STREAM)
        widths = stream_width_floors(g0, batches)
    p = get_pattern(STREAM_PATTERN)
    plans = generate_best_sbenu_plans(
        p, GraphStats(STREAM["n"], STREAM["m_init"],
                      delta_edges=STREAM["batch"]))
    log(phase, stream=json.dumps(STREAM).replace(" ", ""), pattern=p.name,
        width_floors=widths, setup_s=rec["wall_s"])
    return p, plans, g0, batches, widths


def stream_deltas(engine: str, p, plans, g0, batches, widths, phase: str,
                  **backend_kwargs) -> List[tuple]:
    """ΔR± match sets per step of ``engine`` over the stream."""
    from repro.core.executor import SBenuDistBackend, SBenuJaxBackend
    from repro.core.sbenu import run_timestep
    from repro.graph.dynamic import SnapshotStore
    store = SnapshotStore(g0)
    backend = None
    if engine != "sbenu":
        cls = SBenuJaxBackend if engine == "sbenu-jax" else SBenuDistBackend
        backend = cls(collect="matches", d_min=widths[0],
                      delta_d_min=widths[1], **backend_kwargs)
    out = []
    for step, batch in enumerate(batches, 1):
        with timed() as rec:
            plus, minus, _ = run_timestep(
                p, plans, store, batch, engine=engine, collect="matches",
                chunk=STREAM_CHUNK, backend=backend)
        log(phase, run=engine, step=step, plus=len(plus),
            minus=len(minus), wall_s=rec["wall_s"],
            compile_s=rec["compile_s"], peak_bytes_in_use=peak_bytes(),
            host_peak_rss=host_peak_rss())
        out.append((plus, minus))
    return out


def phase_stream(seed: int) -> None:
    stream = make_stream(seed, "c")
    want = stream_deltas("sbenu", *stream, phase="c")
    got = stream_deltas("sbenu-jax", *stream, phase="c")
    check(got == want, "sbenu-jax ΔR± != interpreter")
    log("c", check="sbenu-jax == sbenu interpreter (match sets)", ok=True)


# --------------------------------------------------------------------------
# --four-chips
# --------------------------------------------------------------------------


def phase_four_chips(seed: int) -> None:
    import jax
    from repro.core.executor import make_executor
    from repro.core.pattern import get_pattern
    from repro.core.plangen import generate_best_plan
    g, tri = static_graph(seed, "4")
    plan = generate_best_plan(get_pattern("triangle"), g.stats())
    caps = STATIC_CAPS["triangle"]
    st, rec = run_static("jax", plan, g, batch=STATIC_BATCH, caps=caps)
    log_stats("4", "jax/triangle", st, rec)
    single = st.count
    ex = make_executor("dist", hot=DIST_HOT, rebalance=True,
                       req_cap=DIST_REQ_CAP)
    with timed() as rec:
        st = ex.run(plan, g, batch=STATIC_BATCH, caps=DIST_CAPS)
    shard_devs = {s.device for s in ex.backend.shards.addressable_shards}
    log_stats("4", "dist/triangle", st, rec, hot=DIST_HOT, rebalance=True,
              caps=",".join(map(str, DIST_CAPS)), req_cap=ex.backend.req_cap,
              shard_devices=len(shard_devs),
              per_shard_counts=",".join(
                  map(str, st.extras["per_shard_counts"].tolist())))
    for d in jax.devices():
        ms = d.memory_stats() or {}
        log("4", device=d.id, bytes_in_use=ms.get("bytes_in_use"),
            peak_bytes_in_use=ms.get("peak_bytes_in_use"))
    check(len(shard_devs) == len(jax.devices()),
          f"row shards on {len(shard_devs)} of {len(jax.devices())} chips")
    check(st.count == single == tri,
          f"dist {st.count} / jax {single} / numpy {tri} disagree")
    log("4", check="dist == jax == numpy (triangle)", ok=True)
    stream = make_stream(seed, "4")
    want = stream_deltas("sbenu-jax", *stream, phase="4")
    got = stream_deltas("sbenu-dist", *stream, phase="4")
    check(got == want, "sbenu-dist ΔR± != sbenu-jax")
    log("4", check="sbenu-dist == sbenu-jax (match sets)", ok=True)


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only dist / sbenu-dist on a 4-chip host")
    args = ap.parse_args(argv)
    log("setup", compile_cache=enable_compile_cache(), seed=args.seed)
    import jax
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    want = 4 if args.four_chips else 1
    try:
        dev = check_device(want)
        if args.four_chips:
            check(dev["count"] == 4, f"--four-chips on {dev['count']} chips")
            phase_four_chips(args.seed)
        else:
            phase_small(args.seed)
            phase_static(args.seed)
            phase_stream(args.seed)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log("cuts", sizes="none: every phase ran at the size its docstring names")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
