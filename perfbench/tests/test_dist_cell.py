"""The four-chip ``dist`` census cell (``census-tri-skpa-dist``) as the
manifest has it, on the CPU at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest perfbench/tests

A traced run of the real entries on four forced CPU devices comes out
correct and reads the distributed DBQ's metrics; the all-to-all share's
reader is checked on a four-chip trace record, since a CPU run's trace
has no TPU plane.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import tracing  # noqa: E402
from test_bench import SEED, tiny  # noqa: E402,F401
from test_progtrace import reader  # noqa: E402

CELL = "census-tri-skpa-dist"
NEW = ("a2a_share.dist", "a2a_fill.dist")


def test_manifest_names_the_dist_cell_in_its_metrics():
    import manifest
    bench = manifest.Manifest()
    cell = bench.workload(CELL)
    cfg = bench.config(cell["config"])
    assert (cell["chips"], cfg["engine"], cfg["shards"]) == (4, "dist", 4)
    assert cfg["n_vertices"] == cfg["source_values"]["n_vertices"]
    layer = {m["name"] for m in bench.per_layer(CELL)}
    assert set(NEW) <= layer
    assert {"enu_fill.census", "int_roofline_share.census"} <= layer
    assert [m["name"] for m in bench.end_to_end(CELL)] == [
        "census_matches_per_s", "setup_s"]


def test_tiny_dist_cell_traced_is_correct(tiny, tmp_path):
    """The real entries, cut as the tiny fixture cuts every cell, with the
    hot rows cut in proportion to the vertices (else every row would be
    hot and no row would cross the exchange)."""
    root = tmp_path / "r"
    shutil.copytree(tiny.root, root)
    name = tiny.workload(CELL)["config"]
    path = root / tiny._named("configs", name)["file"]
    cfg = json.loads(path.read_text())
    full = cfg["source_values"]["n_vertices"]
    cfg["engine_args"]["hot"] = max(
        1, cfg["engine_args"]["hot"] * cfg["n_vertices"] // full)
    path.write_text(json.dumps(cfg))
    code = f"""
import argparse, json, sys
sys.path.insert(0, {str(BENCH)!r})
import manifest, run
bench = manifest.Manifest({str(root)!r})
args = argparse.Namespace(workload={CELL!r}, seed={SEED}, seconds=1.0,
                          trace=1)
print(json.dumps(run.run(args, require_chip=False, bench=bench)))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["correct"], res
    assert res["device"]["count"] == 4
    assert res["checks"]["tasks_wrong"]["value"] == 0
    assert res["attempted"] > 1 and res["failed"] == 0
    assert "engine=dist shards=4" in out.stderr
    fill = res["metrics"]["a2a_fill.dist"]["value"]
    assert 0 < fill <= 100
    for name, m in res["metrics"].items():
        if m["unit"] == "%":
            assert 0 <= m["value"] <= 100, (name, m)


def _four_chip_trace():
    """A 1-ms window over four chips: each runs a 0.4-ms fusion and two
    all-to-alls of 0.1 ms (chip 3's second one half outside the
    window)."""
    device = {}
    for c in range(4):
        t = 1_000_000
        evs = [("%fusion.6 = s32[24577]{0} fusion(%all_to_all.20)", t,
                400_000),
               ("%all_to_all.19 = s32[4,1,8192]{2,1,0} all-to-all(...)",
                t + 400_000, 100_000)]
        second = t + 500_000 if c < 3 else t + 950_000
        evs.append(("%all_to_all.20 = s32[4,8192,4096]{2,1,0} "
                    "all-to-all(%broadcast_select_fusion.1)", second,
                    100_000))
        device[f"/device:TPU:{c}"] = evs
    host = [("bench.window", 1_000_000, 1_000_000)]
    return {"device": device, "host": host}


def test_a2a_share_reads_all_to_all_time_per_chip():
    trace = _four_chip_trace()
    summary = tracing.reduce_trace(trace, *tracing.window_bounds(trace))
    got = reader("a2a_share.dist")({"trace": summary})
    # (4 x 0.1 + 3 x 0.1 + 0.05) ms over 4 chips, over 1 ms
    assert abs(got - 100 * 0.75 / 4 / 1.0) < 1e-9
    assert 0 <= got <= 100


def test_dist_readers_say_none_without_their_input():
    trace = _four_chip_trace()
    for c in trace["device"]:
        trace["device"][c] = trace["device"][c][:1]
    summary = tracing.reduce_trace(trace, *tracing.window_bounds(trace))
    assert reader("a2a_share.dist")({"trace": summary}) is None
    assert reader("a2a_share.dist")({}) is None
    assert reader("a2a_fill.dist")({}) is None
    # a window in which no drive call counted the exchange
    assert reader("a2a_fill.dist")({"window": (0.0, 0.0)}) is None
