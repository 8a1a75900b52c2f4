"""Tests of the readers of the program's own record (progtrace.py), on the
CPU: the xplane metadata reader on a hand-built XSpace, the scope rule, the
trace metrics on a recorded chip excerpt that carries scopes, the span and
counter metrics on recorded ``repro.obs`` spans, and None (never an error)
wherever the input is missing.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest perfbench/tests
"""

from __future__ import annotations

import importlib.util
import json
import struct
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import progtrace  # noqa: E402
from repro import obs  # noqa: E402
from test_bench import STREAM, _run, tiny  # noqa: E402,F401

EXCERPTS = BENCH / "tests" / "data"
NEW = {"census-tri-skpa": ["enu_share.census"],
       "stream-q1p-pa": ["step_idle_share.stream",
                         "delta_buffers_ms_p50.stream",
                         "snapshot_place_ms_p50.stream",
                         "snapshot_h2d_mb.stream", "dbq_device_ms.stream",
                         "derive_device_ms.stream"]}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- xplane


def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _len(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, x):
    return _varint(field << 3) + _varint(x)


def _stat(sid, s=None, ref=None, dbl=None):
    body = _int(1, sid)
    if s is not None:
        body += _len(5, s.encode())
    if ref is not None:
        body += _int(7, ref)
    if dbl is not None:
        body += _varint(2 << 3 | 1) + struct.pack("<d", dbl)
    return body


def _plane(name, events, stat_names, lines=b""):
    body = _int(1, 7) + _len(2, name.encode()) + lines
    for eid, (ename, stats) in enumerate(events, 1):
        meta = _int(1, eid) + _len(2, ename.encode()) + b"".join(
            _len(5, s) for s in stats)
        body += _len(4, _int(1, eid) + _len(2, meta))
    for sid, sname in stat_names.items():
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                            + _len(2, sname.encode())))
    return body


def test_metadata_reader_on_a_built_xspace(tmp_path):
    names = {1: "tf_op", 2: "hlo_category", 3: "flops", 4: "convolution"}
    dev = _plane("/device:TPU:0", [
        ("%fusion.6 = s32[8] fusion()", [_stat(1, "jit(run)/enu/scatter"),
                                         _stat(2, ref=4), _stat(3, dbl=2.0)]),
        ("%copy.1 = s32[8] copy()", [])], names,
        lines=_len(3, b"\x0a\x03abc"))
    host = _plane("/host:CPU", [("%x", [_stat(1, "jit(run)/int/x")])],
                  names)
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(_len(1, host) + _len(1, dev) + _int(3, 5))
    meta = progtrace._op_metadata(pb)
    assert list(meta) == ["/device:TPU:0"]        # device planes only
    got = meta["/device:TPU:0"]
    assert got["%fusion.6 = s32[8] fusion()"] == {
        "tf_op": "jit(run)/enu/scatter", "hlo_category": "convolution"}
    assert got["%copy.1 = s32[8] copy()"] == {}


@pytest.mark.parametrize("stats,module,want", [
    ({"tf_op": "jit(run)/enu/jit(cumsum)/add"}, "jit_run", "enu"),
    ({"tf_op": "jit(run)/int/jit(sorted_intersect_pallas)/x"}, "", "int"),
    ({"tf_op": "jit(run)/dbq/concatenate"}, "jit_run", "dbq"),
    ({}, "jit_derive", "derive"),                 # the program's name
    ({"tf_op": "jit(run)/while"}, "jit_run", None),
    ({}, "jit_sorted_intersect_pallas", None),    # "int" only as a word
])
def test_scope_of(stats, module, want):
    assert progtrace.scope_of(stats, module) == want


# --------------------------------------------------------------- trace reads


def _excerpt(cell):
    return json.loads((EXCERPTS / f"progtrace_{cell}.json").read_text())


def _with_trace(monkeypatch, rec):
    monkeypatch.setattr(progtrace, "load", lambda *a, **k: rec)
    return {"trace": {"window_s": 1.0}, "trace_dir": Path("unread"),
            "window": (0.0, 1e12)}


def test_synthetic_trace_reads():
    rec = {"device": {"/device:TPU:0": [
        ["a", 100, 50, "enu"], ["b", 120, 60, "dbq"], ["c", 300, 100, None],
        ["d", 2000, 10, "enu"]]},
        "host": [["bench.window", 0, 1000], ["repro.timestep", 50, 300],
                 ["repro.timestep", 600, 200], ["repro.timestep", 1500, 9]]}
    s = progtrace.scope_seconds(rec)
    assert s == {"enu": pytest.approx(50e-9), "dbq": pytest.approx(60e-9),
                 None: pytest.approx(100e-9)}
    assert progtrace.host_spans(rec, "timestep") == [(50, 350), (600, 800)]
    u = progtrace.busy_union(rec)
    assert u.tolist() == [[100, 180], [300, 400], [2000, 2010]]
    assert progtrace.covered(u, 50, 350) == 130


def test_per_chip_scope_reads_on_a_four_chip_trace(monkeypatch):
    """Four device planes with 100, 200, 300 and 400 ns under ``enu`` in a
    1,000-ns window with two steps, and a fifth whose only op lies outside
    the window: the per-chip readings are the mean over the four."""
    rec = {"device": {f"/device:TPU:{i}": [["a", 0, 100 * (i + 1), "enu"],
                                           ["b", 500, 40, "dbq"]]
                      for i in range(4)},
           "host": [["bench.window", 0, 1000], ["repro.timestep", 0, 400],
                    ["repro.timestep", 400, 400]]}
    rec["device"]["/device:TPU:4"] = [["a", 2000, 100, "enu"]]
    summed = progtrace.scope_seconds(rec)
    assert summed["enu"] == pytest.approx(1000e-9)
    per = progtrace.scope_seconds_per_chip(rec)
    assert per == {"enu": pytest.approx(250e-9), "dbq": pytest.approx(40e-9)}
    ctx = _with_trace(monkeypatch, rec)
    ctx["trace"]["window_s"] = 1000e-9
    assert reader("enu_share.census")(ctx) == pytest.approx(25.0)
    assert reader("dbq_device_ms.stream")(ctx) == pytest.approx(20e-6)


def test_step_idle_share_on_synthetic_trace(monkeypatch):
    rec = {"device": {"/device:TPU:0": [["a", 100, 100, "enu"]]},
           "host": [["bench.window", 0, 1000], ["repro.timestep", 0, 400]]}
    ctx = _with_trace(monkeypatch, rec)
    assert reader("step_idle_share.stream")(ctx) == pytest.approx(75.0)
    assert reader("dbq_device_ms.stream")(ctx) is None   # no dbq op


@pytest.mark.parametrize("cell", sorted(NEW))
def test_trace_readers_on_recorded_chip_excerpt(monkeypatch, cell):
    """A few hundred ms of a traced window on a v5e, ops tagged with their
    scope (the excerpt's window ends with its last op)."""
    rec = _excerpt(cell)
    ctx = _with_trace(monkeypatch, rec)
    scopes = progtrace.scope_seconds(rec)
    # one device plane: the per-chip readings are the summed ones, exactly
    assert progtrace.scope_seconds_per_chip(rec) == scopes
    busy = sum(scopes.values())
    scoped = sum(v for k, v in scopes.items() if k is not None)
    assert scoped >= 0.9 * busy
    if cell.startswith("census"):
        v = reader("enu_share.census")(ctx)
        assert 0 < v <= 100
        assert scopes["enu"] > scopes.get("int", 0)
    else:
        idle = reader("step_idle_share.stream")(ctx)
        assert 0 < idle < 100
        assert reader("dbq_device_ms.stream")(ctx) > 0
        assert reader("derive_device_ms.stream")(ctx) > 0


# --------------------------------------------------------------- obs reads


def test_span_and_counter_readers_per_step():
    obs.reset()
    for t in (1, 2, 3):
        with obs.span("timestep", key=t):
            for _ in range(2):                  # both directions
                with obs.span("snapshot.delta_buffers"):
                    pass
                with obs.span("snapshot.place"):
                    obs.count("snapshot.h2d_bytes", 1_000_000 * t)
    ctx = {"window": (0.0, 1e12)}
    recs = obs.records()
    per = {}
    for r in recs:
        if r.name == "snapshot.place":
            per[r.key] = per.get(r.key, 0) + (r.t1_ns - r.t0_ns) * 1e-6
    assert reader("snapshot_place_ms_p50.stream")(ctx) == \
        pytest.approx(sorted(per.values())[1])
    assert reader("delta_buffers_ms_p50.stream")(ctx) > 0
    assert reader("snapshot_h2d_mb.stream")(ctx) == pytest.approx(4.0)
    # steps outside the window do not count
    assert reader("snapshot_h2d_mb.stream")({"window": (0.0, 1e-9)}) is None
    obs.reset()


@pytest.mark.parametrize("name", sorted(n for v in NEW.values() for n in v))
def test_readers_return_none_without_input(monkeypatch, tmp_path, name):
    obs.reset()
    read = reader(name)
    assert read({}) is None                                 # untraced
    assert read({"trace": {"window_s": 1.0}, "trace_dir": tmp_path,
                 "window": (0.0, 1.0)}) is None              # no trace
    # a program without repro.obs, or without scopes in its trace
    monkeypatch.setattr(progtrace, "_obs", lambda: None)
    rec = {"device": {"/device:TPU:0": [["x", 10, 5, None]]},
           "host": [["bench.window", 0, 100]]}
    ctx = _with_trace(monkeypatch, rec)
    assert read(ctx) is None


@pytest.mark.parametrize("workload", STREAM)
def test_tiny_traced_stream_reads_program_spans(tiny, workload):  # noqa: F811
    """On the CPU: the span and counter metrics read a number; the device
    ones read nothing (the CPU trace has no device plane)."""
    res = _run(tiny, workload, trace=1)
    got = res["metrics"]
    for name in ("delta_buffers_ms_p50.stream",
                 "snapshot_place_ms_p50.stream", "snapshot_h2d_mb.stream"):
        assert got[name]["value"] > 0, name
    assert "dbq_device_ms.stream" not in got
