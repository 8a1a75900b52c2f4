#!/usr/bin/env python3
"""The controls that the correctness checks have to fail.

    python3 perfbench/control.py --workload <name> --seeds <n> [<n> ...]

The control is the plain reference put in the program's place with one
guarantee of the configuration broken, at the cell's own size, compared
by the same check a run makes:

* census cells - "an overflowing chunk is re-split or re-run, never
  truncated": each task keeps the count of a truncated level-1 frontier,
  as a driver that accepted an overflowing chunk's partial result would
  (capacities halved, so every task of this size overflows);
* stream cells - "ΔR- holds the matches of the graph before the batch":
  ΔR- is taken on the graph after the batch, as an engine that dropped
  the previous snapshot to save its copy would.

Prints one JSON line per seed: the verdict and the numbers the run's
check compares, each beside its limit.
The benchmark's runs never run this; it sets the upper readings in
PERF.md.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import graphgen  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

#: tasks per census control: about what a 51 s window runs today
CENSUS_TASKS = 112


def truncated_counts(cc: reference.CliqueCounter, tasks: np.ndarray,
                     k: int, cap: int) -> np.ndarray:
    """Per-task counts when only the first ``cap`` level-1 rows (start,
    neighbour) of each task are kept."""
    out = np.zeros(tasks.shape[0], np.int64)
    for i, row in enumerate(tasks):
        v = row[row >= 0]
        lens = cc.fdeg[v]
        keep = np.cumsum(lens) <= cap
        part = v[keep]
        # the first row of the task that crosses the cap keeps a prefix
        rest = cap - int(lens[keep].sum())
        out[i] = cc.counts(part, k).sum()
        j = int(keep.sum())
        if j < v.shape[0] and rest > 0:
            out[i] += _prefix_count(cc, int(v[j]), rest, k)
    return out


def _prefix_count(cc, v: int, rest: int, k: int) -> int:
    """Cliques of ``v`` whose second vertex is among v's first ``rest``
    forward neighbours."""
    nb = cc.fidx[cc.fptr[v]:cc.fptr[v] + rest]
    fv = set(cc.fidx[cc.fptr[v]:cc.fptr[v + 1]].tolist())
    total = 0
    for a in nb.tolist():
        common = [b for b in cc.fidx[cc.fptr[a]:cc.fptr[a + 1]].tolist()
                  if b in fv]
        if k == 3:
            total += len(common)
        else:
            cs = set(common)
            total += sum(1 for b in common
                         for c in cc.fidx[cc.fptr[b]:cc.fptr[b + 1]].tolist()
                         if c in cs)
    return total


def census_control(cfg, mix, seed: int) -> dict:
    import census
    ip, ix = graphgen.static_graph(
        cfg["structure_seed"], cfg["n_vertices"], cfg["avg_degree"],
        cfg["gamma"], cfg["max_degree"], cfg["closure_share"],
        cfg["hub_overshoot"], label_seed=seed)
    tasks = graphgen.task_order(seed, cfg["n_vertices"],
                                mix["task_size"])[:CENSUS_TASKS]
    k = census.PATTERN_K[mix["pattern"]]
    got = truncated_counts(reference.CliqueCounter(ip, ix), tasks, k,
                           mix["caps"][0] // 2)
    return census._check(ip, ix, tasks, got, k)


def stream_control(cfg, mix, seed: int, seconds: float) -> dict:
    import stream
    steps = mix["warm_steps"] + math.ceil(seconds * 1e3 / mix["period_ms"])
    st = graphgen.edge_stream(seed, cfg["n_vertices"], cfg["m0"], steps,
                              cfg["batch"], cfg["delete_share"],
                              cfg["gamma"], cfg["hub_degree"])
    # op 0 still removes the edge but is not looked up as a delete:
    # ΔR- comes out as matches of the graph after the batch, where the
    # deleted edges are gone
    stale = list(reference.q1p_deltas(
        st.n, st.g0_src, st.g0_dst,
        [(np.maximum(st.ops[k], 0).astype(np.int8), st.src[k], st.dst[k])
         for k in range(steps)]))
    return stream._check(st, stale)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float,
                    default=float(manifest.Manifest().data["run_seconds"]))
    args = ap.parse_args()
    bench = manifest.Manifest()
    cell = bench.workload(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    for seed in args.seeds:
        t = time.perf_counter()
        if mix["kind"] == "census":
            checks = census_control(cfg, mix, seed)
        else:
            checks = stream_control(cfg, mix, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": run.verdict(0, checks),
                          "checks": checks,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
