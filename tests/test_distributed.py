"""Distribution tests: run in subprocesses with forced host device counts
(the main pytest process must keep the default single device — dry-run
policy). Covers the rowstore all_to_all fetch, distributed enumeration
(exactness, hot rows, rebalancing), and int8-compressed gradient psum."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # forced host devices, off-chip
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_distributed_enumeration_exact_with_all_features():
    out = run_sub("""
        import json, numpy as np
        from repro.core.pattern import get_pattern
        from repro.core.plangen import generate_best_plan
        from repro.core.ref_engine import enumerate_matches_brute
        from repro.core.engine_dist import enumerate_distributed
        from repro.core.symmetry import symmetry_breaking_constraints
        from repro.graph.generate import powerlaw
        g = powerlaw(120, 4, seed=4)
        res = {}
        for pname in ("triangle", "chordal-square", "house"):
            P = get_pattern(pname)
            plan = generate_best_plan(P, g.stats())
            brute = len(enumerate_matches_brute(
                P, g, symmetry_breaking_constraints(P)))
            st = enumerate_distributed(plan, g, batch_per_shard=16,
                                       hot=16, rebalance=True)
            st0 = enumerate_distributed(plan, g, batch_per_shard=16)
            res[pname] = dict(
                brute=brute, dist=st.count, plain=st0.count,
                cold_hot=st.cold_rows_fetched,
                cold_plain=st0.cold_rows_fetched,
                skew_reb=int(st.per_shard_level_sizes[-1].max()
                             - st.per_shard_level_sizes[-1].min())
                if len(st.per_shard_level_sizes) else 0)
        print(json.dumps(res))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    for pname, r in res.items():
        assert r["dist"] == r["brute"] == r["plain"], (pname, r)
        # hot-row replication strictly reduces remote traffic
        assert r["cold_hot"] <= r["cold_plain"], (pname, r)


@pytest.mark.slow
def test_rowstore_fetch_unit():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp, json
        from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
        from jax import shard_map
        from repro.distributed.rowstore import (build_row_shards,
                                                make_distributed_fetch)
        from repro.graph.generate import erdos_renyi
        g = erdos_renyi(100, 300, seed=0)
        S = 8
        shards_np, hot_np, spec = build_row_shards(g, S, hot=8)
        mesh = Mesh(np.array(jax.devices()), ("s",))
        fetch = make_distributed_fetch(spec, "s", req_cap=32)
        B = 16
        rng = np.random.default_rng(0)
        ids = rng.integers(0, g.n, size=(S, B)).astype(np.int32)

        def local(shards, hot, ids):
            rows, cold, drops = fetch(ids[0], shards[0], hot)
            return rows[None], cold[None], drops[None]

        f = jax.jit(shard_map(
            local, mesh=mesh,
            in_specs=(P("s", None, None), P(None, None), P("s", None)),
            out_specs=(P("s", None, None), P("s"), P("s")),
            check_vma=False))
        rows, cold, drops = f(shards_np, hot_np, ids)
        rows = np.asarray(rows).reshape(S * B, spec.d)
        want = np.concatenate([shards_np.reshape(-1, spec.d)])
        ok = True
        flat_ids = ids.reshape(-1)
        for i, v in enumerate(flat_ids):
            exp = want[v]
            ok &= np.array_equal(rows[i], exp)
        print(json.dumps({"ok": bool(ok), "drops": int(np.sum(drops)),
                          "cold": int(np.sum(cold))}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"] and res["drops"] == 0


# --------------------------------------------------------------------------
# DistributedRowStore hot-row boundary (in-process, single-device mesh:
# S=1 makes the all_to_all a local exchange, so this stays in the fast
# tier). The fetch must match the unsharded padded-adjacency oracle with
# ids exactly at n_hot_lo, with zero hot rows, and with every row hot.
# --------------------------------------------------------------------------


def _fetch_rows(g, hot, ids, req_cap=64):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.distributed.rowstore import (build_row_shards,
                                            make_distributed_fetch)
    import numpy as np
    shards_np, hot_np, spec = build_row_shards(g, 1, hot=hot)
    mesh = Mesh(np.array(jax.devices()[:1]), ("s",))
    fetch = make_distributed_fetch(spec, "s", req_cap=req_cap)

    def local(shards, hot_rows, ids):
        rows, cold, drops = fetch(ids[0], shards[0], hot_rows)
        return rows[None], cold[None], drops[None]

    f = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P("s", None, None), P(None, None), P("s", None)),
        out_specs=(P("s", None, None), P("s"), P("s")),
        check_vma=False))
    rows, cold, drops = f(shards_np, hot_np, ids[None].astype(np.int32))
    import numpy as _np
    oracle = shards_np.reshape(-1, spec.d)[:spec.n + 1]
    return (_np.asarray(rows)[0], int(_np.sum(_np.asarray(cold))),
            int(_np.sum(_np.asarray(drops))), spec, oracle)


@pytest.mark.parametrize("hot", [0, 8, 100])   # zero / partial / all hot
def test_rowstore_hot_boundary_matches_unsharded_oracle(hot):
    import numpy as np
    from repro.graph.generate import erdos_renyi
    g = erdos_renyi(100, 300, seed=0)
    n_hot_lo = g.n - min(hot, g.n)
    # ids straddling the boundary: n_hot_lo - 1 (cold side), n_hot_lo
    # (first hot row), n_hot_lo + 1, plus extremes and the sentinel
    cand = [0, 1, n_hot_lo - 1, n_hot_lo, n_hot_lo + 1, g.n - 1, g.n]
    ids = np.array([i for i in cand if 0 <= i <= g.n], np.int64)
    ids = np.pad(ids, (0, 16 - ids.size), constant_values=g.n)
    rows, cold, drops, spec, oracle = _fetch_rows(g, hot, ids)
    assert drops == 0
    assert spec.hot == min(hot, g.n)
    for i, v in enumerate(ids):
        np.testing.assert_array_equal(rows[i], oracle[v], err_msg=str(v))
    # hot rows are served locally: they never count as cold traffic
    want_cold = len({int(v) for v in ids if v < n_hot_lo})
    assert cold == want_cold


def test_rowstore_all_hot_serves_everything_locally():
    import numpy as np
    from repro.graph.generate import powerlaw
    g = powerlaw(60, 3, seed=5)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, g.n + 1, size=32).astype(np.int64)
    rows, cold, drops, spec, oracle = _fetch_rows(g, hot=g.n, ids=ids)
    assert cold == 0 and drops == 0       # every row replicated
    for i, v in enumerate(ids):
        np.testing.assert_array_equal(rows[i], oracle[v])


def test_rowstore_zero_hot_all_requests_remote():
    import numpy as np
    from repro.graph.generate import erdos_renyi
    g = erdos_renyi(50, 150, seed=3)
    ids = np.arange(16, dtype=np.int64)
    rows, cold, drops, spec, oracle = _fetch_rows(g, hot=0, ids=ids)
    assert drops == 0
    assert cold == 16                     # no replication: all cold
    for i, v in enumerate(ids):
        np.testing.assert_array_equal(rows[i], oracle[v])


@pytest.mark.slow
def test_int8_compressed_psum_error_feedback():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp, json
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.distributed.compression import (compressed_psum,
                                                   plain_psum_mean)
        mesh = Mesh(np.array(jax.devices()), ("d",))
        rng = np.random.default_rng(0)
        g = rng.normal(size=(8, 64)).astype(np.float32)

        def step(gl, err):
            r1 = plain_psum_mean({"w": gl}, "d")
            r2, err2 = compressed_psum({"w": gl}, "d", {"w": err})
            return r1["w"][None], r2["w"][None], err2["w"][None]

        f = jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P("d", None), P("d", None)),
            out_specs=(P("d", None), P("d", None), P("d", None)),
            check_vma=False))
        err = np.zeros_like(g)
        rel_errs = []
        carry = 0.0
        for t in range(4):
            exact, comp, err = map(np.asarray, f(g, err))
            err = err.reshape(g.shape)
            rel = np.abs(comp[0] - exact[0]).max() / np.abs(exact[0]).max()
            rel_errs.append(float(rel))
        print(json.dumps({"rel_errs": rel_errs}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    # int8 quantization: single-step error ~1/127; EF keeps it bounded
    assert all(r < 0.05 for r in res["rel_errs"]), res


@pytest.mark.slow
def test_production_mesh_construction():
    out = run_sub("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        print(dict(m1.shape), dict(m2.shape))
    """, devices=512, timeout=180)
    assert "'data': 16, 'model': 16" in out
    assert "'pod': 2" in out
