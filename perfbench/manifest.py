"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``BENCHMARK.json``'s ``file``; a traffic mix is
``traffic/<traffic>.json``; a per-layer metric is read by
``metrics/<name>.py``, whose ``read(ctx)`` returns the number or None.
Adding a cell, a configuration, a mix or a metric adds files and entries;
nothing here changes. A census configuration also names its engine and
the chips that share its rows (``engine``, ``shards``; census.py), so a
four-chip census cell is a configuration file with ``"engine": "dist",
"shards": 4`` and a cell with ``"chips": 4``, named in the metrics it
reports: files and entries only.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class Manifest:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench_dir = self.root / self.data["paths"][0]

    def _named(self, key: str, name: str) -> Dict:
        for entry in self.data[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key} entry named {name!r}")

    def workload(self, name: str) -> Dict:
        return self._named("workloads", name)

    def config(self, name: str) -> Dict:
        return json.loads(
            (self.root / self._named("configs", name)["file"]).read_text())

    def traffic(self, name: str) -> Dict:
        return json.loads(
            (self.bench_dir / "traffic" / f"{name}.json").read_text())

    def end_to_end(self, workload: str) -> List[Dict]:
        """End-to-end metrics the cell reports: those that list it, and
        those that list no cells."""
        return [m for m in self.data["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[Dict]:
        """Per-layer metrics read in the cell's traced run: those that list
        it, and those that list no cells and move one of its end-to-end
        metrics."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.data["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str) -> Callable[[Dict], Optional[float]]:
        path = self.bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "perfbench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
