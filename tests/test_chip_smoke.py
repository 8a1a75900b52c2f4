"""chip_smoke.py off the chip: it must refuse to report without a TPU or
with a kernel override, its numpy oracle must be right, and its phases must
run end to end at a tiny size (Pallas kernels in interpret mode) so the
script stays in step with the engines it drives."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


@pytest.mark.parametrize("override", [None, "REPRO_INTERSECT_IMPL",
                                      "REPRO_FUSED_FETCH"])
def test_refuses_without_tpu_or_with_override(smoke, monkeypatch, capsys,
                                              override):
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "off")
    for var in smoke.impl_overrides():
        monkeypatch.delenv(var)
    if override:
        monkeypatch.setenv(override, "ref")
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert _no_result(out.out)
    assert ("kernel overrides" if override else "no TPU") in out.err


def test_numpy_triangles_matches_ref_engine(smoke):
    from repro.core.executor import make_executor
    from repro.core.pattern import get_pattern
    from repro.core.plangen import generate_best_plan
    from repro.graph.generate import powerlaw
    g = powerlaw(300, 4, seed=3)
    plan = generate_best_plan(get_pattern("triangle"), g.stats())
    assert smoke.numpy_triangles(g) == make_executor("ref").run(plan, g).count


def test_phases_run_tiny_in_interpret_mode(smoke, monkeypatch, capsys):
    monkeypatch.setenv("REPRO_INTERSECT_IMPL", "pallas-interpret")
    monkeypatch.setenv("REPRO_GATHER_INTERSECT_IMPL", "pallas-interpret")
    monkeypatch.setattr(smoke, "STATIC_N", 300)
    monkeypatch.setattr(smoke, "STATIC_BATCH", 64)
    monkeypatch.setattr(smoke, "STATIC_CAPS",
                        {"triangle": (64, 64),
                         "chordal-square": (64, 128, 128)})
    monkeypatch.setattr(smoke, "STREAM",
                        dict(n=200, m_init=1000, steps=2, batch=80))
    monkeypatch.setattr(smoke, "STREAM_CHUNK", 32)
    smoke.phase_small(0, n=60)
    counts = smoke.phase_static(0)
    smoke.phase_stream(0)
    out = capsys.readouterr().out
    assert counts["triangle"] > 0 and "chunks_split=" in out
    assert out.count("ok=True") == 3
