import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x shape) cell on
the production mesh and extract roofline terms from the compiled artifact.

The lines above MUST stay the first statements of this module (jax locks
the platform and the device count at first init); do not move them below
the imports. The 512 devices are forced host devices, so the CPU platform
is pinned: on a chip machine JAX would otherwise pick the attached chips
and ``make_production_mesh`` would find too few.

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    python -m repro.launch.dryrun --arch benu --shape enum_128m --multi-pod
    python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]

Per cell it records (results/dryrun/<arch>__<shape>__<mesh>.json):
    memory_analysis   bytes per device (argument/output/temp/generated)
    cost_analysis     HLO flops / bytes accessed (per-device partition)
    collective_bytes  sum of operand bytes of every all-gather / all-reduce
                      / reduce-scatter / all-to-all / collective-permute in
                      the post-SPMD optimized HLO, by op kind
    roofline          the three §Roofline terms (seconds) + dominant term
"""

import argparse
import json
import math
import re
import time
from typing import Dict, Optional

import jax

# TPU v5e hardware constants (per chip) — §Roofline
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link

def analyze_cell(arch: str, shape: str, multi_pod: bool,
                 sharding_mode: str = "fsdp") -> Dict:
    from .mesh import make_production_mesh
    from .steps import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, multi_pod=multi_pod,
                      sharding_mode=sharding_mode)
    lowered = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis() or {})
    hlo = compiled.as_text()

    # loop-aware accounting (XLA's cost_analysis counts while bodies once —
    # useless for scan-over-layers models); see hlo_analysis.py
    from .hlo_analysis import analyze as hlo_analyze
    tot = hlo_analyze(hlo)
    flops = tot.flops
    bytes_acc = tot.hbm_bytes
    coll_bytes = tot.coll_operand_total
    coll = {k: int(v) for k, v in tot.coll_operand_bytes.items()}
    coll["count"] = tot.coll_count
    coll_wire = {k: int(v) for k, v in tot.coll_wire_bytes.items()}

    # every quantity is per-chip (the compiled module is one SPMD partition)
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_acc / HBM_BW
    t_collective = tot.coll_wire_total / ICI_BW
    dom = max((("compute", t_compute), ("memory", t_memory),
               ("collective", t_collective)), key=lambda kv: kv[1])[0]

    meta = cell.meta
    dims = meta.get("dims", {})
    tokens = 0
    if meta["family"] == "lm":
        if meta["kind"] == "lm_train":
            tokens = dims["seq"] * dims["batch"]
        elif meta["kind"] == "lm_prefill":
            tokens = dims["seq"] * dims["batch"]
        else:
            tokens = dims["batch"]
    model_flops = 0.0
    if meta["family"] == "lm":
        mult = 6 if meta["kind"] == "lm_train" else 2
        model_flops = mult * meta["n_active_params"] * tokens
    useful_ratio = (model_flops / (flops * n_chips)
                    if flops > 0 else 0.0)

    report = {
        "arch": arch, "shape": shape,
        "mesh": ("2x16x16 pod,data,model" if multi_pod
                 else "16x16 data,model"),
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      + mem.output_size_in_bytes),
        },
        "cost_analysis": {"flops_per_chip": flops,
                          "bytes_per_chip": bytes_acc,
                          "xla_flops_loops_once": float(
                              cost.get("flops", 0.0)),
                          "xla_bytes_loops_once": float(
                              cost.get("bytes accessed", 0.0))},
        "collectives": coll,
        "collectives_wire": coll_wire,
        "roofline": {
            "compute_s": t_compute, "memory_s": t_memory,
            "collective_s": t_collective, "dominant": dom,
            "model_flops": model_flops,
            "useful_flops_ratio": useful_ratio,
        },
        "sharding_mode": sharding_mode,
        "meta": {k: v for k, v in meta.items() if k != "plan"},
    }
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-benu", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--sharding-mode", default="fsdp",
                    choices=["fsdp", "zero1", "fsdp2d"],
                    help="LM train-cell parameter layout (see "
                         "launch/shardings.py and EXPERIMENTS.md §Perf)")
    args = ap.parse_args()

    from ..configs import all_cells
    cells = (all_cells(include_benu=args.include_benu) if args.all
             else [(args.arch, args.shape)])
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in cells:
        tag = "multipod" if args.multi_pod else "pod"
        name = f"{arch.replace('/', '_')}__{shape}__{tag}"
        try:
            rep = analyze_cell(arch, shape, args.multi_pod,
                               sharding_mode=args.sharding_mode)
            path = os.path.join(args.out, name + ".json")
            with open(path, "w") as f:
                json.dump(rep, f, indent=1)
            r = rep["roofline"]
            print(f"OK   {name}: compile {rep['compile_s']}s "
                  f"mem/dev {rep['memory_analysis']['peak_bytes_per_device']/2**30:.2f}GiB "
                  f"compute {r['compute_s']*1e3:.2f}ms "
                  f"memory {r['memory_s']*1e3:.2f}ms "
                  f"coll {r['collective_s']*1e3:.2f}ms -> {r['dominant']}",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append((name, str(e)[:300]))
            print(f"FAIL {name}: {str(e)[:300]}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: "
                         f"{[n for n, _ in failures]}")


if __name__ == "__main__":
    main()
