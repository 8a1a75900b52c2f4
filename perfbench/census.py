"""Census cells: time-bounded census tasks over a graph held on the chips.

The configuration names the engine (``engine``: ``jax-gpu``, the
default, or ``dist``) and the chips that share the rows (``shards``,
default 1; the cell's ``chips`` equals it), and may pass the engine's
own options (``engine_args``, such as ``dist``'s ``hot`` rows). What the
census needs of each engine sits in :data:`ENGINES`, so a census cell on
either engine, on one chip or four, is added with files and entries
alone.

Set-up generates the graph from the seed, builds the engine's backend
and prepares it once, which puts the graph in HBM (B-BENU's graph
database: ``jax-gpu``'s row table and its lane-row copy on one chip, or
``dist``'s rows sharded over the mesh with the hot rows on every chip),
then warms the one chunk shape the window uses. The window runs tasks -
fixed-size blocks of start vertices in a seeded random order over all of
V, B-BENU's local-search task queue - each through the Executor's driver
(``drive``), until ``seconds`` have passed, and stops at the first task
boundary after that. A window that runs out of tasks starts the same
order again, so it always lasts ``seconds``. Every task that ran is then
checked against the plain reference (reference.py).
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import numpy as np

import graphgen
import reference
import workmodel
from cell import CellOutput
from tracing import Spans

PATTERN_K = {"triangle": 3, "q3": 4}


@dataclass(frozen=True)
class Engine:
    """What the census needs of one engine's backend."""

    backend: str                       # class in repro.core.executor
    mesh: bool                         # takes a mesh over the cell's chips
    arrays: Callable[[Any], tuple]     # device arrays that hold the graph
    width: Callable[[Any], int]        # row width (lanes)


ENGINES = {
    "jax-gpu": Engine("JaxGpuBackend", False,
                      lambda b: (b.dg.rows, b.dg.lane_rows),
                      lambda b: b.dg.d),
    "dist": Engine("DistBackend", True,
                   lambda b: (b.shards, b.hot_rows),
                   lambda b: b.spec.d),
}


def layout(cfg: Dict) -> Tuple[str, int]:
    """The engine the configuration names and the chips that share its
    rows: ``jax-gpu`` on one chip where it names neither."""
    return cfg.get("engine", "jax-gpu"), cfg.get("shards", 1)


def task_backend(cfg: Dict, spans: Spans):
    """The configuration's engine over its first ``shards`` chips, prepared
    once; ``drive`` then runs one task (the start vertices set in
    ``task``) per call. Returns the backend and its :class:`Engine`."""
    import jax
    from repro.core import executor
    from repro.core.engine_dist import enumeration_mesh

    name, shards = layout(cfg)
    engine = ENGINES[name]
    devices = jax.devices()[:shards]
    if len(devices) != shards or (shards > 1 and not engine.mesh):
        raise ValueError(f"{engine.backend} cannot hold the rows on "
                         f"{shards} chips ({len(jax.devices())} present)")
    kwargs = dict(cfg.get("engine_args", {}))
    if engine.mesh:
        kwargs["mesh"] = enumeration_mesh(devices=devices)

    class TaskBackend(getattr(executor, engine.backend)):
        task = None
        _prepared = False

        def prepare(self, plan, source, config):
            if not self._prepared:
                super().prepare(plan, source, config)
                self._prepared = True

        def start_batches(self, config):
            yield self.task

        def run_chunk(self, ids, valid, universe_chunk, caps):
            with spans.span("chunk"):
                return super().run_chunk(ids, valid, universe_chunk, caps)

    return TaskBackend(**kwargs), engine


def enu_rows(backend) -> int:
    """Frontier rows the ENU levels produced over the backend's accepted
    chunks so far: its level sizes (``[levels]`` on one chip, ``[levels,
    shards]`` on a mesh; None before the first chunk), summed."""
    acc = backend._level_acc
    return 0 if acc is None else int(acc.sum())


def run_cell(cfg: Dict, mix: Dict, seed: int, seconds: float,
             spans: Spans, capture) -> CellOutput:
    """One census run; ``capture`` is the profiler context for the
    window (a no-op when not tracing)."""
    import jax
    from repro.core.executor import ExecutorConfig, drive
    from repro.core.pattern import get_pattern
    from repro.core.plangen import generate_best_plan
    from repro.graph.storage import Graph

    log: Dict[str, object] = {}
    t = time.perf_counter()
    n = cfg["n_vertices"]
    indptr, indices = graphgen.static_graph(
        cfg["structure_seed"], n, cfg["avg_degree"], cfg["gamma"],
        cfg["max_degree"], cfg["closure_share"], cfg["hub_overshoot"],
        label_seed=seed)
    graph = Graph(n, np.split(indices, indptr[1:-1]))
    log["gen_s"] = time.perf_counter() - t
    k = PATTERN_K[mix["pattern"]]
    plan = generate_best_plan(get_pattern(mix["pattern"]), graph.stats())
    B = mix["task_size"]
    config = ExecutorConfig(batch=B, caps=tuple(mix["caps"]))
    tasks = graphgen.task_order(seed, n, B)
    backend, engine = task_backend(cfg, spans)
    engine_name, shards = layout(cfg)

    def set_task(row: np.ndarray) -> None:
        # a task is padded to a multiple of the engine's start-batch
        # granularity (the mesh size for dist), known once prepared
        row = np.pad(row, (0, -row.shape[0] % backend.granularity),
                     constant_values=-1)
        valid = row >= 0
        backend.task = (np.where(valid, row, n).astype(np.int32), valid)

    t = time.perf_counter()
    backend.prepare(plan, graph, config)
    jax.block_until_ready(engine.arrays(backend))
    log["upload_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for row in tasks[-1 - mix["warm_tasks"]:-1]:   # full tasks off the end
        set_task(row)
        drive(backend, plan, graph, config)
    log["warm_s"] = time.perf_counter() - t
    log.update(n=n, m=int(indices.shape[0] // 2),
               max_degree=int(np.diff(indptr).max()),
               engine=engine_name, shards=shards,
               width=engine.width(backend),
               pattern=mix["pattern"], task_size=B, caps=mix["caps"],
               instrs=len(plan.instrs))

    counts, ran, failed = [], [], 0
    chunks = splits = retries = 0
    rows0 = enu_rows(backend)
    with capture():
        window_start = time.perf_counter()
        with spans.span("window"):
            for i in itertools.count():
                row = tasks[i % tasks.shape[0]]
                set_task(row)
                try:
                    with spans.span("task"):
                        st = drive(backend, plan, graph, config)
                except Exception as e:                 # noqa: BLE001
                    # a task that raises is a failed answer, not a crash
                    log.setdefault("task_errors", []).append(repr(e)[:200])
                    failed += 1
                    counts.append(-1)
                else:
                    counts.append(int(st.count))
                    chunks += st.chunks_run
                    splits += st.chunks_split
                    retries += st.chunks_retried
                ran.append(i % tasks.shape[0])
                if time.perf_counter() - window_start >= seconds:
                    break
        window_s = time.perf_counter() - window_start
    got = np.asarray(counts, np.int64)
    matches = int(got[got >= 0].sum())
    run_tasks = tasks[ran]
    accepted = chunks - splits - retries
    # the capacity the engine applied: its caps, per shard on a mesh
    capacity = accepted * shards * sum(backend.initial_caps(config))
    log.update(tasks=len(ran), window_s=window_s, matches=matches,
               chunks_run=chunks, chunks_split=splits,
               chunks_retried=retries)
    return CellOutput(
        attempted=len(ran), failed=failed,
        e2e={"census_matches_per_s": matches / window_s},
        layer={"spans": spans, "window_s": window_s,
               "enu_rows": enu_rows(backend) - rows0,
               "enu_capacity_rows": int(capacity),
               "least_int_bytes": (workmodel.triangle_int_bytes(
                   indptr, indices, run_tasks)
                   if mix["pattern"] == "triangle" else None)},
        log=log,
        check=lambda: _check(indptr, indices, run_tasks, got, k))


def _check(indptr, indices, run_tasks, got, k) -> Dict[str, tuple]:
    """Each task's count against the reference's: the number of tasks
    whose count differs (limit 0), and the summed absolute error."""
    ref = reference.CliqueCounter(indptr, indices)
    want = ref.counts(run_tasks.ravel(), k).reshape(run_tasks.shape).sum(1)
    wrong = int(np.count_nonzero(got != want))
    return {"tasks_wrong": (wrong, 0),
            "count_abs_error": (int(np.abs(got - want).sum()), 0)}
