#!/usr/bin/env python3
"""Offered-rate sweep of a stream cell, to find its knee once.

    python3 perfbench/sweep.py --workload stream-q1p-pa --seed <n> \
        --seconds 10 --periods 50 60 70 85

Runs the cell's window once per period, in one process, on the chip, and
prints one JSON line per period: the offered and completed updates per
second, step latency p50 / p95, the median service time, and how much
later the last step finished than the first (a backlog that grows). The
knee is the shortest period whose backlog does not grow. Not part of any
benchmark run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import devicekit  # noqa: E402
import manifest  # noqa: E402
import stream  # noqa: E402
from tracing import Spans  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--periods", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench = manifest.Manifest()
    cell = bench.workload(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    devicekit.setup_compile_cache()
    devicekit.check_device(cell["chips"])
    for p in args.periods:
        out = stream.run_cell(cfg, dict(mix, period_ms=p), args.seed,
                              args.seconds, Spans(annotate=False),
                              contextlib.nullcontext)
        log = out.log
        print(json.dumps({
            "period_ms": p, "offered_updates_per_s": cfg["batch"] * 1e3 / p,
            **out.e2e, "stream_step_p95_ms": log["step_ms_p95"],
            "service_ms_p50": log["service_ms_p50"],
            "last_step_ms": log["last_step_ms"],
            "worst_step": log["worst_step"],
            "gc_gen2_in_window": log["gc_gen2_in_window"],
            "window_s": log["window_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
