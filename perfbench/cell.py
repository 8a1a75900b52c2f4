"""What one run of a cell hands back to the harness."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Tuple


@dataclass
class CellOutput:
    """``attempted``/``failed``: units of work (tasks, steps) the window
    ran and those that raised or answered wrong; ``e2e``: end-to-end
    readings by metric name (set-up time is the harness's); ``layer``:
    what the per-layer readers read; ``log``: set-up and window figures
    for standard error; ``check``: the comparison with the reference, run
    once the window has closed, returning ``{name: (value, limit)}``."""

    attempted: int
    failed: int
    e2e: Dict[str, float]
    layer: Dict[str, object]
    log: Dict[str, object]
    check: Callable[[], Dict[str, Tuple[float, float]]]
