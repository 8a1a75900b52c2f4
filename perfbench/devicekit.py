"""Device record, memory readings, compile counting and the peaks table.

``check_device`` refuses to run anywhere but on the chips a cell asks
for; nothing falls back to the CPU. Import this module before JAX is
touched: :func:`setup_compile_cache` has to run first.
"""

from __future__ import annotations

import json
import os
import resource
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
#: JAX's persistent compilation cache: one fixed path inside the checkout
CACHE_DIR = HERE.parent / ".bench_cache" / "jax"

#: compile seconds = lowering to MLIR + the XLA/Mosaic backend compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a kernel override in force."""


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at :data:`CACHE_DIR` (whatever the
    environment says, so that two checkouts never share one) and cache
    every program, however fast it compiles. The directory holds this
    checkout's programs only, so it is not size-bounded: with a bound,
    JAX's eviction reads an access-time file for every entry, and one
    missing file makes every later write fail."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Compile seconds, backend compiles, lowerings (a program built,
    compiled or read from the persistent cache) and persistent-cache hits,
    read from ``jax.monitoring`` events (tracing left out of the seconds:
    nested jits would count twice)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = self.lowerings = self.cache_hits = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += secs
        if event == BACKEND_COMPILE:
            self.compiles += 1
        elif event == LOWERING:
            self.lowerings += 1

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self):
        return (self.compiles, self.lowerings, self.cache_hits)


def impl_overrides() -> List[str]:
    """Environment overrides of the kernel choice, which a run refuses."""
    return sorted(k for k in os.environ
                  if (k.startswith("REPRO_") and k.endswith("_IMPL"))
                  or k == "REPRO_FUSED_FETCH")


def check_device(want_count: int) -> Dict[str, object]:
    """The device record, or :class:`DeviceError` off the chip."""
    over = impl_overrides()
    if over:
        raise DeviceError(f"kernel overrides set: {over}")
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise DeviceError(f"no TPU: JAX found {dev['platform']}")
    if dev["count"] < want_count:
        raise DeviceError(f"need {want_count} chips, found {dev['count']}")
    from repro.kernels.dispatch import resolve_impl
    impls = {op: resolve_impl(op) for op in ("intersect", "gather_intersect")}
    if any(v != "pallas" for v in impls.values()):
        raise DeviceError(f"INT kernels must resolve to Pallas: {impls}")
    return dev


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def host_peak_rss() -> int:
    """This process's peak resident host memory, bytes (Linux ru_maxrss
    is in KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind`` (peaks.json); a kind
    that is not in the table is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]
