"""Tests of the benchmark harness, on the CPU at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest perfbench/tests

They cover the trace reduction on a small recorded chip trace and its
per-chip readings, the least-bytes model, the manifest finding cells,
configurations, mixes and metric readers by name, a four-chip ``dist``
census cell added from files alone (on forced CPU devices), the
references against brute force and against the program's per-task
counts, and runs whose timed path is broken underneath, which must come
out not correct.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import census  # noqa: E402
import control  # noqa: E402
import graphgen  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import tracing  # noqa: E402
import workmodel  # noqa: E402

SEED = 2**31 + 17          # above 32 signed bits, as run seeds may be


# --------------------------------------------------------------- tiny cells


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A manifest whose cells are the real ones at tiny sizes: the real
    traffic files and metric readers, configurations cut to 2,000
    vertices."""
    root = tmp_path_factory.mktemp("tiny")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", root / "perfbench" / "metrics")
    for c in data["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        if "avg_degree" in cfg:
            cfg.update(n_vertices=2000, max_degree=128)
        else:
            cfg.update(n_vertices=2000, m0=12600, batch=50, hub_degree=60)
        (root / c["file"]).write_text(json.dumps(cfg))
    for w in data["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        if t["kind"] == "census":
            t.update(task_size=64, caps=[1024, 2048, 8192][:len(t["caps"])])
        else:
            t.update(period_ms=20, chunk=64, warm_steps=2, trace_seconds=0.3)
        (root / "perfbench" / "traffic" / f"{w['traffic']}.json"
         ).write_text(json.dumps(t))
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return manifest.Manifest(root)


def _run(bench, workload, trace=0, seconds=1.0):
    import run
    args = argparse.Namespace(workload=workload, seed=SEED,
                              seconds=seconds, trace=trace)
    return run.run(args, require_chip=False, bench=bench)


CENSUS = ["census-tri-skpa"]
STREAM = ["stream-q1p-pa"]


@pytest.mark.parametrize("workload", CENSUS + STREAM)
def test_tiny_run_is_correct(tiny, workload):
    """A whole run on the CPU: the window's per-task counts (owned by each
    clique's smallest vertex) and per-step deltas equal the reference."""
    res = _run(tiny, workload)
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 1
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in tiny.end_to_end(workload)}
    assert set(res["metrics"]) == want


@pytest.mark.parametrize("workload,always", [
    ("census-tri-skpa", "enu_fill.census"),
    ("stream-q1p-pa", "snapshot_ms_p50.stream")])
def test_tiny_traced_run_reports_layer_metrics(tiny, workload, always):
    """The stream's mix traces only the steps due in the first 0.3 s of
    its 1-s window. It is judged by the steps in the trace: on a CPU a
    tiny step can take longer than the 20-ms period, so how long the
    traced steps last depends on the machine's load."""
    import run
    res = _run(tiny, workload, trace=1)
    assert res["correct"]
    assert set(res["metrics"]) <= {m["name"] for m in
                                   tiny.per_layer(workload)}
    assert always in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    if workload.startswith("stream"):
        mix = tiny.traffic(tiny.workload(workload)["traffic"])
        trace = tracing.load_trace(tiny.root / run.TRACE_DIR / workload)
        steps = [n for n, _, _ in trace["host"] if n == "bench.step"]
        traced = math.ceil(mix["trace_seconds"] * 1e3 / mix["period_ms"])
        assert len(steps) == traced
        assert res["attempted"] - mix["warm_steps"] > 2 * traced


def _break(monkeypatch, fault):
    """Plant one fault under the timed path."""
    from repro.core import executor
    from repro.graph import dynamic
    if fault == "answer_altered":
        orig = executor.JaxBackend.run_chunk

        def run_chunk(self, ids, valid, uni, caps):
            r = orig(self, ids, valid, uni, caps)
            r.count += 1
            return r
        monkeypatch.setattr(executor.JaxBackend, "run_chunk", run_chunk)
    elif fault == "half_batch":
        orig = executor.JaxBackend.run_chunk

        def run_chunk(self, ids, valid, uni, caps):
            valid = valid.copy()
            valid[np.flatnonzero(valid)[1::2]] = False
            return orig(self, ids, valid, uni, caps)
        monkeypatch.setattr(executor.JaxBackend, "run_chunk", run_chunk)
    elif fault == "state_unchanged":
        def end_step(self):
            self.delta_out, self.delta_in = {}, {}
        monkeypatch.setattr(dynamic.SnapshotStore, "end_step", end_step)
    elif fault == "half_updates":
        orig = dynamic.SnapshotStore.begin_step

        def begin_step(self, batch):
            return orig(self, list(batch)[: len(batch) // 2])
        monkeypatch.setattr(dynamic.SnapshotStore, "begin_step", begin_step)
    elif fault == "delta_altered":
        orig = executor.SBenuJaxBackend.finalize

        def finalize(self, stats):
            orig(self, stats)
            if stats.extras["delta_plus"]:
                stats.extras["delta_plus"].pop()
        monkeypatch.setattr(executor.SBenuJaxBackend, "finalize", finalize)


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CENSUS for f in ("answer_altered", "half_batch")] + [
    (w, f) for w in STREAM
    for f in ("state_unchanged", "half_updates", "delta_altered")])
def test_broken_timed_path_is_not_correct(tiny, monkeypatch, workload,
                                          fault):
    _break(monkeypatch, fault)
    res = _run(tiny, workload)
    assert not res["correct"], res
    assert res["failed"] > 0


@pytest.mark.parametrize("workload", CENSUS + STREAM)
def test_control_fails_the_check(tiny, workload):
    """The control (the reference with a guarantee broken), compared by
    the run's own check and judged by the run's own rule, is not
    correct."""
    import run
    cell = tiny.workload(workload)
    cfg, mix = tiny.config(cell["config"]), tiny.traffic(cell["traffic"])
    if mix["kind"] == "census":
        mix = dict(mix, caps=[2 * 64 * 6] + mix["caps"][1:])
        checks = control.census_control(cfg, mix, SEED)
    else:
        checks = control.stream_control(cfg, mix, SEED, 1.0)
    assert not run.verdict(0, checks), checks
    assert run.wrong_units(checks) > 0


# --------------------------------------------------------------- references


def _brute_cliques(indptr, indices, k):
    adj = [set(indices[indptr[v]:indptr[v + 1]].tolist())
           for v in range(indptr.shape[0] - 1)]
    own = np.zeros(len(adj), np.int64)
    for v in range(len(adj)):
        fw = sorted(u for u in adj[v] if u > v)
        for rest in itertools.combinations(fw, k - 1):
            if all(b in adj[a] for a, b in itertools.combinations(rest, 2)):
                own[v] += 1
    return own


@pytest.mark.parametrize("k", [3, 4])
def test_clique_reference_matches_brute_force(k):
    ip, ix = graphgen.static_graph(SEED, 300, 13.08, 2.3, 64, 0.3, 1.5)
    got = reference.CliqueCounter(ip, ix).counts(np.arange(300), k)
    np.testing.assert_array_equal(got, _brute_cliques(ip, ix, k))


def test_clique_reference_blocks_do_not_change_counts(monkeypatch):
    ip, ix = graphgen.static_graph(SEED, 500, 13.08, 2.3, 64, 0.3, 1.5)
    starts = graphgen.task_order(SEED, 500, 64).ravel()
    want = reference.CliqueCounter(ip, ix).counts(starts, 4)
    monkeypatch.setattr(reference, "BLOCK_WEDGES", 50)
    got = reference.CliqueCounter(ip, ix).counts(starts, 4)
    np.testing.assert_array_equal(got, want)
    assert (got[starts < 0] == 0).all()


def test_q1p_reference_matches_snapshot_diff():
    """Against brute-force enumeration of both snapshots per batch."""
    st = graphgen.edge_stream(SEED, 60, 300, 4, 40, 0.3, 2.3, 20)
    batches = [(st.ops[k], st.src[k], st.dst[k]) for k in range(4)]
    edges = set(zip(st.g0_src.tolist(), st.g0_dst.tolist()))

    def cycles(es):
        out = set()
        for a, b in es:
            for c in range(60):
                if (b, c) in es and (c, a) in es and a < b and a < c:
                    out.add((a * 60 + b) * 60 + c)
        return out

    for (wp, wm), (op, s, d) in zip(
            reference.q1p_deltas(60, st.g0_src, st.g0_dst, batches),
            batches):
        before = cycles(edges)
        for o, a, b in zip(op.tolist(), s.tolist(), d.tolist()):
            (edges.add if o > 0 else edges.discard)((a, b))
        after = cycles(edges)
        assert set(wp.tolist()) == after - before
        assert set(wm.tolist()) == before - after


def test_generators_are_seeded_and_shaped():
    a = graphgen.static_graph(SEED, 3000, 13.08, 2.3, 256, 0.3, 1.5)
    b = graphgen.static_graph(SEED, 3000, 13.08, 2.3, 256, 0.3, 1.5)
    np.testing.assert_array_equal(a[1], b[1])
    deg = np.diff(a[0])
    assert deg.max() == 256                     # fixed row width
    assert (np.diff(deg) >= 0).all()            # degree order
    # another label seed: the same graph relabelled, the same work
    c = graphgen.static_graph(SEED, 3000, 13.08, 2.3, 256, 0.3, 1.5,
                              label_seed=5)
    assert not np.array_equal(a[1], c[1])
    np.testing.assert_array_equal(np.diff(c[0]), deg)
    assert (reference.CliqueCounter(*a).counts(np.arange(3000), 3).sum()
            == reference.CliqueCounter(*c).counts(np.arange(3000), 3).sum())
    st = graphgen.edge_stream(SEED, 500, 3000, 5, 100, 0.3, 2.3, 40)
    assert all(o.shape == (100,) and (o < 0).sum() == 30 for o in st.ops)
    keys = set(zip(st.g0_src.tolist(), st.g0_dst.tolist()))
    for op, s, d in zip(st.ops, st.src, st.dst):
        for o, x, y in zip(op.tolist(), s.tolist(), d.tolist()):
            assert ((x, y) in keys) == (o < 0)
        for o, x, y in zip(op.tolist(), s.tolist(), d.tolist()):
            (keys.add if o > 0 else keys.discard)((x, y))


# --------------------------------------------------------------- work model


def test_least_bytes_model_counts_real_lengths():
    ip, ix = graphgen.static_graph(SEED, 400, 13.08, 2.3, 64, 0.3, 1.5)
    tasks = graphgen.task_order(SEED, 400, 64)[:3]
    deg = np.diff(ip)
    want = 0
    for v in tasks.ravel():
        if v < 0:
            continue
        for a in ix[ip[v]:ip[v + 1]]:
            if a > v:
                want += 4 * (deg[v] + deg[a])
    assert workmodel.triangle_int_bytes(ip, ix, tasks) == want


# --------------------------------------------------------------- manifest


def test_manifest_finds_everything_by_name():
    bench = manifest.Manifest()
    names = [w["name"] for w in bench.data["workloads"]]
    for w in names:
        cell = bench.workload(w)
        assert bench.config(cell["config"])["name"] == cell["config"]
        assert bench.traffic(cell["traffic"])["kind"] in ("census",
                                                          "stream")
        assert any(m["name"] == "setup_s" for m in bench.end_to_end(w))
        assert len(bench.end_to_end(w)) >= 2
        assert bench.per_layer(w)
        for m in bench.per_layer(w):
            assert callable(bench.reader(m["name"]))
    for m in bench.data["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench.data["end_to_end"]}


def test_cell_chips_are_its_configuration_shards():
    """The chips a cell asks for are the chips its configuration shards
    the rows over (one where it names none): one source for the count."""
    bench = manifest.Manifest()
    for w in bench.data["workloads"]:
        cfg = bench.config(w["config"])
        assert w["chips"] == census.layout(cfg)[1], w["name"]


def test_census_layout_defaults_to_jax_gpu_on_one_chip():
    """A configuration that names no engine and no shards (skitter-pa)
    runs on ``jax-gpu`` on one chip."""
    from repro.core.executor import JaxGpuBackend
    cfg = manifest.Manifest().config("skitter-pa")
    assert "engine" not in cfg and "shards" not in cfg
    assert census.layout(cfg) == ("jax-gpu", 1)
    backend, engine = census.task_backend(cfg, tracing.Spans(False))
    assert isinstance(backend, JaxGpuBackend)
    assert engine is census.ENGINES["jax-gpu"]
    with pytest.raises(ValueError):        # jax-gpu holds one chip's rows
        census.task_backend(dict(cfg, shards=2), tracing.Spans(False))


def test_manifest_takes_new_cells_from_files_alone(tiny, tmp_path):
    """A new mix, cell and per-layer metric need files and entries only."""
    root = tmp_path / "r"
    shutil.copytree(tiny.root, root)
    data = json.loads((root / "BENCHMARK.json").read_text())
    mix = json.loads((root / "perfbench/traffic/census-tri.json").read_text())
    mix["task_size"] = 32
    (root / "perfbench/traffic/census-tri-small.json").write_text(
        json.dumps(mix))
    data["workloads"].append({"name": "census-tri-small",
                              "config": "skitter-pa",
                              "traffic": "census-tri-small", "chips": 1,
                              "why": "test"})
    (root / "perfbench/metrics/tasks_run.census.py").write_text(
        "def read(ctx):\n    return float(ctx['enu_capacity_rows'] > 0)\n")
    data["per_layer"].append({"name": "tasks_run.census", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "driver and device",
                              "moves": "census_matches_per_s",
                              "workloads": ["census-tri-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    bench = manifest.Manifest(root)
    assert bench.traffic("census-tri-small")["task_size"] == 32
    res = _run(bench, "census-tri-small", trace=1)
    assert res["correct"]
    assert res["metrics"]["tasks_run.census"]["value"] == 1.0


def _add_dist_census(root: Path) -> str:
    """Add a four-chip ``dist`` census cell to the manifest at ``root``
    with files and entries only: a configuration file and its entry, the
    cell, and the cell named in the metrics that list the census cell."""
    data = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "perfbench/configs/skitter-pa.json").read_text())
    cfg.update(name="skitter-pa-dist", engine="dist", shards=4,
               engine_args={"hot": 16, "rebalance": True})
    (root / "perfbench/configs/skitter-pa-dist.json").write_text(
        json.dumps(cfg))
    data["configs"].append(dict(data["configs"][0], name="skitter-pa-dist",
                                file="perfbench/configs/skitter-pa-dist.json"))
    cell = "census-tri-skpa-dist"
    data["workloads"].append({"name": cell, "config": "skitter-pa-dist",
                              "traffic": "census-tri", "chips": 4,
                              "why": "test"})
    for m in data["end_to_end"] + data["per_layer"]:
        if "census-tri-skpa" in m.get("workloads", []):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return cell


def test_four_chip_dist_census_from_files_alone(tiny, tmp_path):
    """A ``dist`` census cell on four (forced CPU) devices, added from files
    and entries alone: untraced and traced, correct, with every share in
    [0, 100]."""
    root = tmp_path / "r"
    shutil.copytree(tiny.root, root)
    cell = _add_dist_census(root)
    code = f"""
import argparse, json, sys
sys.path.insert(0, {str(BENCH)!r})
import manifest, run
bench = manifest.Manifest({str(root)!r})
for trace in (0, 1):
    args = argparse.Namespace(workload={cell!r}, seed={SEED}, seconds=1.0,
                              trace=trace)
    print(json.dumps(run.run(args, require_chip=False, bench=bench)))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    plain, traced = (json.loads(x) for x in out.stdout.splitlines()[-2:])
    for res in (plain, traced):
        assert res["correct"], res
        assert res["device"]["count"] == 4
        assert res["checks"]["tasks_wrong"]["value"] == 0
        assert res["attempted"] > 1 and res["failed"] == 0
    assert set(plain["metrics"]) == {"census_matches_per_s", "setup_s"}
    assert "enu_fill.census" in traced["metrics"]
    for name, m in traced["metrics"].items():
        if m["unit"] == "%":
            assert 0 <= m["value"] <= 100, (name, m)
    assert "engine=dist shards=4" in out.stderr


# --------------------------------------------------------------- trace


def test_reduction_on_synthetic_trace():
    tr = {"device": {"/device:TPU:0": [["a", 100, 50], ["b", 120, 60],
                                        ["a", 300, 100]]},
          "host": [["bench.window", 0, 1000], ["bench.task", 150, 200]]}
    lo, hi = tracing.window_bounds(tr)
    s = tracing.reduce_trace(tr, lo, hi)
    assert s["busy_s"] == pytest.approx(180e-9)      # [100,180) + [300,400)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["op_s"]["a"] == pytest.approx(150e-9)
    assert s["idle_gaps"][0] == ["none", pytest.approx(600e-9)]
    assert s["idle_gaps"][1] == ["task", pytest.approx(120e-9)]


def test_reduction_on_recorded_chip_trace():
    """A 300 ms excerpt of a traced census window on a v5e."""
    tr = json.loads((BENCH / "tests" / "data" / "trace_excerpt.json")
                    .read_text())
    dev = next(iter(tr["device"].values()))
    lo = min(e[1] for e in dev)
    hi = max(e[1] + e[2] for e in dev)
    s = tracing.reduce_trace(tr, lo, hi)
    assert 0 < s["busy_s"] <= s["window_s"]
    k = tracing.kernel_seconds(s, ("intersect_pallas",))
    assert k > 0
    # one device plane: the per-chip reading is the summed one, exactly
    assert s["chips"] == 1
    assert tracing.kernel_seconds_per_chip(s, ("intersect_pallas",)) == k
    names = [n for n, _ in s["device_ops"]]
    assert any("gather_intersect_pallas" in n for n in names)
    # the union never exceeds the summed op time
    assert s["busy_s"] <= sum(s["op_s"].values()) + 1e-12


def test_per_chip_seconds_on_a_four_chip_trace():
    """Five device planes: three ran 100, 200 and 300 ns of ``k`` in a
    1,000-ns window, one only another op, one nothing inside the window.
    The per-chip reading is the sum over the four planes that ran
    anything there, over four."""
    tr = {"device": {
        "/device:TPU:0": [["%k.1 = s32[] k()", 100, 100]],
        "/device:TPU:1": [["%k.1 = s32[] k()", 100, 200]],
        "/device:TPU:2": [["%k.1 = s32[] k()", 100, 300]],
        "/device:TPU:3": [["%other = s32[] o()", 0, 400]],
        "/device:TPU:4": [["%k.1 = s32[] k()", 5000, 100]]},
        "host": [["bench.window", 0, 1000]]}
    s = tracing.reduce_trace(tr, *tracing.window_bounds(tr))
    assert s["chips"] == 4
    assert tracing.kernel_seconds(s, ("k.",)) == pytest.approx(600e-9)
    assert tracing.kernel_seconds_per_chip(s, ("k.",)) == \
        pytest.approx(150e-9)
    assert tracing.kernel_seconds_per_chip(
        dict(s, chips=0), ("k.",)) == 0.0
