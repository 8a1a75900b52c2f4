#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; ``kind`` in the mix picks the driver (census.py,
stream.py). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from the profiler's trace of
the window and from the benchmark's spans. Inputs come from ``--seed``.
Every run checks the window's answers against the plain reference and
prints each number compared beside its limit, last on standard error and
last in the result line (``checks``). Off the chip, or on fewer chips
than the cell asks for, it exits non-zero without a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, Iterator, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import devicekit  # noqa: E402
import manifest  # noqa: E402
import tracing  # noqa: E402

#: traces go under the manifest's root, one directory per cell
TRACE_DIR = Path(".bench_cache") / "trace"


class Window:
    """The profiler context a cell opens around its window; notes when
    set-up ended and the window closed, and the compiles in between."""

    def __init__(self, counter, logdir: Optional[Path]):
        self.counter, self.logdir = counter, logdir
        self.start = self.end = 0.0
        self.built = (0, 0, 0)

    @contextlib.contextmanager
    def __call__(self) -> Iterator[Callable[[], None]]:
        with tracing.capture(self.logdir) as stop_trace:
            c0 = self.counter.snapshot()
            self.start = time.perf_counter()
            try:
                yield stop_trace
            finally:
                self.end = time.perf_counter()
                self.built = tuple(b - a for a, b in
                                   zip(c0, self.counter.snapshot()))


def wrong_units(checks: Dict) -> int:
    """Tasks or steps whose answer differs from the reference."""
    return int(checks.get("tasks_wrong", checks.get("steps_wrong",
                                                    (0, 0)))[0])


def verdict(raised: int, checks: Dict) -> bool:
    """``correct``: no unit of work raised and every number compared is
    within its limit."""
    return raised == 0 and all(v <= lim for v, lim in checks.values())


def _fmt(v) -> str:
    return json.dumps(v) if isinstance(v, (list, tuple, dict)) else str(v)


def run(args, require_chip: bool = True,
        bench: Optional[manifest.Manifest] = None) -> Dict:
    """One run; returns the result object. ``require_chip=False`` skips the
    look for a chip, and ``bench`` replaces the manifest: tests drive the
    rest of a run on the CPU at tiny sizes."""
    bench = bench or manifest.Manifest()
    cell = bench.workload(args.workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    devicekit.setup_compile_cache()
    import jax
    counter = devicekit.CompileCounter()
    if require_chip:
        dev = devicekit.check_device(cell["chips"])
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(jax.devices())}
    devices = jax.devices()[:cell["chips"]]
    logdir = None
    if args.trace:
        logdir = bench.root / TRACE_DIR / args.workload
        shutil.rmtree(logdir, ignore_errors=True)
    window = Window(counter, logdir)
    spans = tracing.Spans(annotate=bool(args.trace))
    if mix["kind"] == "census":
        import census as driver
    elif mix["kind"] == "stream":
        import stream as driver
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    out = driver.run_cell(cfg, mix, args.seed, args.seconds, spans, window)
    setup_s = window.start - T_START
    dev["memory_peak_bytes"] = devicekit.peak_bytes(devices)
    say = lambda tag, d: print(  # noqa: E731
        f"[{tag}] " + " ".join(f"{k}={_fmt(v)}" for k, v in d.items()),
        file=sys.stderr, flush=True)
    say("setup", dict(workload=args.workload, seed=args.seed,
                      platform=dev["platform"], kind=dev["kind"],
                      setup_s=setup_s, compile_s=counter.seconds,
                      host_peak_rss=devicekit.host_peak_rss(), **out.log))
    say("window", dict(compiles_in_window=window.built[0],
                       lowerings_in_window=window.built[1],
                       cache_hits_in_window=window.built[2],
                       setup_compiles=counter.compiles - window.built[0],
                       setup_cache_hits=counter.cache_hits
                       - window.built[2],
                       attempted=out.attempted, raised=out.failed,
                       memory_peak_bytes=dev["memory_peak_bytes"],
                       host_peak_rss=devicekit.host_peak_rss()))

    result: Dict = {"device": dev}
    if args.trace:
        trace = tracing.load_trace(logdir)
        lo, hi = tracing.window_bounds(trace)
        summary = tracing.reduce_trace(trace, lo, hi)
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        peaks = devicekit.peaks(dev["kind"]) if require_chip else {}
        ctx = {"trace": summary, "trace_dir": logdir, "peaks": peaks,
               "window": (window.start, window.end), **out.layer}
        metrics = {}
        for m in bench.per_layer(args.workload):
            v = bench.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        readings = dict(out.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": readings[m["name"]],
                               "unit": m["unit"]}
                   for m in bench.end_to_end(args.workload)}

    checks = out.check()
    result.update(correct=verdict(out.failed, checks),
                  attempted=out.attempted,
                  failed=out.failed + wrong_units(checks), metrics=metrics)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    return {k: result[k] for k in order if k in result}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except devicekit.DeviceError as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    except Exception:                                   # noqa: BLE001
        traceback.print_exc()
        return 1
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
