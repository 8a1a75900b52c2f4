"""Distributed subgraph-enumeration launcher (the paper's workload).

    PYTHONPATH=src python -m repro.launch.enumerate \
        --pattern chordal-square --n 2000 --edges 8000 [--devices 8] \
        [--engine dist|jax|jax-gpu|ref|oocache] [--hot 64] [--rebalance] \
        [--vcbc]

``--engine jax-gpu`` runs the accelerator fetch path: single-use DBQ
gathers fuse into the intersect kernel (kernels/gather_intersect.py, see
docs/KERNELS.md) so gathered row blocks never round-trip through HBM; on a
TPU it runs the compiled Pallas kernel; on a CPU-only machine pass
``--gather-intersect-impl interpret`` to run the Pallas kernel in
interpret mode (otherwise it falls back to the unfused reference, still
exact).

``--engine oocache`` runs the out-of-core fetch path: adjacency rows live
in host-RAM shards, device memory holds only a bounded row cache
(``--cache-frac`` of N rows + ``--hot`` pinned top-degree rows) and the
next chunk's rows are prefetched while the current chunk computes; the
report adds hit rate / cold rows / bytes moved per DBQ level.

Generates a synthetic graph, compiles the best execution plan (Alg. 3 with
all optimizations), and runs the chosen engine through the unified
Executor API (core/executor.py) over every device, reporting counts + the
paper's cost metrics (DBQ rows crossed / computation per shard / skew).

Continuous enumeration (S-BENU, Alg. 4) runs the timestep loop instead:

    PYTHONPATH=src python -m repro.launch.enumerate \
        --engine sbenu-jax --pattern "q1'" --n 5000 --edges 25000 \
        --steps 3 --update-batch 500

``--engine sbenu`` interprets every task; ``--engine sbenu-jax`` runs the
vectorized delta-frontier engine over the six-block device snapshot;
``--engine sbenu-dist`` shards the six blocks over every device
(``--devices N`` forces an N-way host mesh) with typed DBQs served by
request/response all_to_all — ``--hot`` rows replicated, ``--rebalance``
striping every delta frontier round-robin across the mesh.
"""

from __future__ import annotations

import argparse
import os
import time


def _run_continuous(args) -> None:
    """Algorithm 4's timestep loop over the chosen S-BENU backend."""
    from ..core.estimate import GraphStats
    from ..core.pattern import get_pattern
    from ..core.sbenu import generate_best_sbenu_plans, run_timestep
    from ..graph.dynamic import SnapshotStore, stream_width_floors
    from ..graph.generate import edge_stream

    P = get_pattern(args.pattern)
    if not P.directed:
        raise SystemExit(f"--engine {args.engine} needs a directed pattern "
                         f"(q1'..q5', dtoy); got {args.pattern!r}")
    g0, batches = edge_stream(n=args.n, m_init=args.edges, steps=args.steps,
                              batch=args.update_batch, seed=args.seed)
    store = SnapshotStore(g0)
    stats = GraphStats(args.n, args.edges, delta_edges=args.update_batch)
    plans = generate_best_sbenu_plans(P, stats)
    print(f"pattern {args.pattern}: {len(plans)} incremental plans "
          f"(one per delta edge)")
    backend = None
    if args.engine == "sbenu-jax":
        # one backend for the whole stream, widths pinned over every step:
        # the JIT engine compiles once instead of retracing per step
        from ..core.executor import SBenuJaxBackend
        d, dd = stream_width_floors(g0, batches)
        backend = SBenuJaxBackend(collect="counts", d_min=d,
                                  delta_d_min=dd,
                                  snapshot_storage=args.snapshot_storage)
    elif args.engine == "sbenu-dist":
        from ..core.executor import SBenuDistBackend
        d, dd = stream_width_floors(g0, batches)
        backend = SBenuDistBackend(collect="counts", d_min=d,
                                   delta_d_min=dd, hot=args.hot,
                                   rebalance=args.rebalance)
    total_p = total_m = 0
    t_all = 0.0
    for step, batch in enumerate(batches, 1):
        t0 = time.time()
        dp, dm, ctr = run_timestep(P, plans, store, batch,
                                   engine=args.engine, backend=backend,
                                   chunk=args.batch_per_shard,
                                   collect="counts")
        dt = time.time() - t0
        t_all += dt
        total_p += ctr.matches_plus
        total_m += ctr.matches_minus
        print(f"step {step}: dR+ {ctr.matches_plus:>8}  "
              f"dR- {ctr.matches_minus:>8}  {dt:6.2f}s  "
              f"{args.update_batch / max(dt, 1e-9):,.0f} updates/s")
    print(f"\nengine             : {args.engine}")
    print(f"total dR+ / dR-    : {total_p} / {total_m}")
    print(f"wall time          : {t_all:.2f}s over {args.steps} steps")
    if args.engine == "sbenu-dist":
        import jax
        print(f"mesh               : {len(jax.devices())} devices "
              f"(hot {args.hot} rows replicated, "
              f"rebalance {'on' if args.rebalance else 'off'})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pattern", default="chordal-square")
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--edges", type=int, default=8000)
    ap.add_argument("--graph", choices=["er", "powerlaw"],
                    default="powerlaw")
    ap.add_argument("--engine",
                    choices=["dist", "jax", "jax-gpu", "ref", "oocache",
                             "sbenu", "sbenu-jax", "sbenu-dist"],
                    default="dist")
    ap.add_argument("--gather-intersect-impl", default="auto",
                    help="jax-gpu: fused kernel impl (auto | pallas | "
                         "interpret | ref/chunked/binary fallbacks); "
                         "'interpret' runs the Pallas kernel on CPU")
    ap.add_argument("--devices", type=int, default=0,
                    help="run on N forced host devices: pins the CPU "
                         "platform (set before jax init)")
    ap.add_argument("--batch-per-shard", type=int, default=256)
    ap.add_argument("--hot", type=int, default=64,
                    help="replicated/pinned hot rows: top-degree for "
                         "dist/oocache (degree-relabeled load); the "
                         "highest-id range for sbenu-dist (streams are "
                         "not relabeled)")
    ap.add_argument("--cache-frac", type=float, default=0.15,
                    help="oocache: device LRU slab size as a fraction of N")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="oocache: disable the async next-chunk prefetch")
    ap.add_argument("--snapshot-storage", choices=["device", "host"],
                    default="device",
                    help="sbenu-jax: 'host' keeps resident blocks in "
                         "host-RAM shards (zero persistent HBM between "
                         "steps; per-step compute still transfers full "
                         "blocks — slower compat path until the OOC "
                         "delta-frontier engine lands)")
    ap.add_argument("--rebalance", action="store_true")
    ap.add_argument("--vcbc", action="store_true")
    ap.add_argument("--steps", type=int, default=3,
                    help="time steps (continuous engines)")
    ap.add_argument("--update-batch", type=int, default=200,
                    help="edge updates per time step (continuous engines)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:
        # forced host devices exist only on the CPU backend: pin it, or a
        # chip machine would silently run on its own devices instead
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.engine in ("sbenu", "sbenu-jax", "sbenu-dist"):
        _run_continuous(args)
        return

    import jax

    from ..core.executor import make_executor
    from ..core.pattern import get_pattern
    from ..core.plangen import generate_best_plan
    from ..graph.generate import erdos_renyi, powerlaw

    P = get_pattern(args.pattern)
    g = (powerlaw(args.n, max(args.edges // args.n, 2), seed=args.seed)
         if args.graph == "powerlaw"
         else erdos_renyi(args.n, args.edges, seed=args.seed))
    plan = generate_best_plan(P, g.stats(), vcbc=args.vcbc)
    print(plan.pretty())

    if args.engine == "dist":
        ex = make_executor("dist", hot=args.hot, rebalance=args.rebalance)
        batch = args.batch_per_shard * len(jax.devices())
    elif args.engine == "oocache":
        ex = make_executor("oocache", cache_frac=args.cache_frac,
                           hot=args.hot, prefetch=not args.no_prefetch)
        batch = args.batch_per_shard
    elif args.engine == "jax-gpu":
        ex = make_executor("jax-gpu",
                           gather_intersect_impl=args.gather_intersect_impl)
        batch = args.batch_per_shard
    else:
        ex = make_executor(args.engine)
        batch = args.batch_per_shard
    t0 = time.time()
    st = ex.run(plan, g, batch=batch)
    dt = time.time() - t0
    print(f"\nengine             : {args.engine}")
    print(f"matches            : {st.count}")
    print(f"wall time          : {dt:.2f}s")
    print(f"chunks run         : {st.chunks_run} "
          f"(split {st.chunks_split}, retried {st.chunks_retried})")
    if args.engine == "dist":
        cold = st.extras["cold_rows_fetched"]
        print(f"cold rows fetched  : {cold} "
              f"(x {plan.n * 4}B row bytes = {cold * 512 / 1e6:.1f}MB class)")
        print(f"per-shard matches  : "
              f"{st.extras['per_shard_counts'].tolist()}")
    elif args.engine == "oocache":
        c = st.extras["cache"]
        print(f"host store         : {st.extras['host_store_bytes'] / 1e6:.1f}MB "
              f"in {st.extras['host_store_shards']} shards")
        print(f"device resident    : {st.extras['device_resident_rows']} rows "
              f"({st.extras['device_resident_bytes'] / 1e6:.2f}MB = "
              f"{st.extras['device_resident_rows'] / (g.n + 1) * 100:.1f}% of N)")
        print(f"row queries        : {c['queries']} ({c['hit_rate'] * 100:.1f}% "
              f"served without a host fetch)")
        print(f"cold rows fetched  : {c['cold_rows']} "
              f"({c['bytes_demand'] / 1e6:.2f}MB demand + "
              f"{c['bytes_prefetch'] / 1e6:.2f}MB prefetch)")
        print(f"prefetch used      : {c['prefetch_used']} rows; "
              f"evictions {c['evictions']}")
        for lvl, (q, cold, b) in c["per_level"].items():
            print(f"  DBQ level {lvl}      : {q:>9} queries  {cold:>8} cold  "
                  f"{b / 1e6:8.2f}MB")
    elif args.engine == "ref":
        print(f"remote DBQ rows    : {st.extras['remote_queries']}")
    elif args.engine in ("jax", "jax-gpu"):
        lv = st.extras["level_sizes"]
        print(f"fused fetch        : "
              f"{'on' if st.extras['fused_fetch'] else 'off'}")
        print(f"frontier rows/level: {lv.tolist()}")


if __name__ == "__main__":
    main()
