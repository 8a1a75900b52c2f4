"""Benchmark driver: one suite per paper table/figure + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [suite ...]
"""

from __future__ import annotations

import sys
import time


# every enumeration suite routes through the unified Executor API
# (repro/core/executor.py) — one chunking/overflow policy across engines
SUITES = ["plan_search", "plan_opts", "cache", "conformance", "task_split",
          "vs_join", "sbenu_bench", "scaling", "roofline"]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    want = sys.argv[1:] or SUITES
    failures = []
    for name in want:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run().show()
            print(f"[{name}: {time.time() - t0:.1f}s]")
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            print(f"[{name} FAILED: {e}]")
    if failures:
        raise SystemExit(f"failed suites: {[n for n, _ in failures]}")


if __name__ == "__main__":
    main()
