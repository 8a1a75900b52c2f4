"""Training loop with checkpoint/restart, failure injection and optional
manual-DP gradient compression.

Two execution modes:

* ``pjit`` (default): the step is jit'd with parameter/optimizer shardings;
  XLA inserts all collectives. This is the mode the multi-pod dry-run
  lowers.
* ``manual_dp``: the step runs under shard_map over the DP axis with an
  explicit gradient psum — required to exercise int8 gradient compression
  with error feedback (distributed/compression.py).

Fault tolerance: the loop checkpoints every ``ckpt_every`` steps through
:class:`~repro.train.checkpoint.CheckpointManager` and starts from
``restore_or_init`` — killing the process at any step and rerunning the
same command resumes bit-exactly (tests/test_train.py does exactly that,
plus an elastic-resharding restart on a different device count).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..distributed.compression import compressed_psum, plain_psum_mean
from .checkpoint import CheckpointManager
from .optimizer import AdamWConfig, AdamWState, adamw_init, adamw_update


@dataclass
class TrainLoopConfig:
    steps: int = 100
    ckpt_every: int = 25
    log_every: int = 10
    fail_at_step: Optional[int] = None        # failure injection (tests)
    grad_compression: Optional[str] = None    # None | "int8" (manual_dp)


def run_training(loss_fn: Callable,
                 init_params_fn: Callable[[], Any],
                 batch_fn: Callable[[int], Dict[str, np.ndarray]],
                 opt_cfg: AdamWConfig,
                 loop_cfg: TrainLoopConfig,
                 ckpt: Optional[CheckpointManager] = None,
                 shardings: Any = None,
                 mesh=None,
                 dp_axis: Optional[str] = None) -> Dict[str, list]:
    """Generic driver used by the examples and the restart tests.

    ``loss_fn(params, batch) -> (loss, metrics)``.
    Returns the metric history (host floats).
    """

    def init_state():
        params = init_params_fn()
        return {"params": params, "opt": adamw_init(params)}

    start_step = 0
    if ckpt is not None:
        state, start_step = ckpt.restore_or_init(init_state, shardings)
    else:
        state = init_state()

    use_manual_dp = (loop_cfg.grad_compression is not None
                     and mesh is not None and dp_axis is not None)

    if use_manual_dp:
        from jax.sharding import PartitionSpec as P

        err0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            state["params"])
        if "err" not in state:
            state["err"] = err0

        def local_step(params, opt, err, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            if loop_cfg.grad_compression == "int8":
                grads, err = compressed_psum(grads, dp_axis, err)
            else:
                grads = plain_psum_mean(grads, dp_axis)
            new_params, new_opt, om = adamw_update(opt_cfg, grads, opt,
                                                   params)
            metrics = dict(metrics)
            metrics.update(om)
            metrics["loss_total"] = jax.lax.pmean(loss, dp_axis)
            return new_params, new_opt, err, metrics

        rep = jax.tree.map(lambda _: P(), state["params"])
        step_fn = jax.jit(shard_map(
            local_step, mesh=mesh,
            in_specs=(rep, jax.tree.map(lambda _: P(), state["opt"]),
                      rep, P(dp_axis)),
            out_specs=(rep, jax.tree.map(lambda _: P(), state["opt"]),
                       rep, P()),
            check_vma=False))
    else:
        def full_step(params, opt, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            new_params, new_opt, om = adamw_update(opt_cfg, grads, opt,
                                                   params)
            metrics = dict(metrics)
            metrics.update(om)
            metrics["loss_total"] = loss
            return new_params, new_opt, metrics

        step_fn = jax.jit(full_step)

    history: Dict[str, list] = {"step": [], "loss": []}
    t0 = time.time()
    for step in range(start_step, loop_cfg.steps):
        if loop_cfg.fail_at_step is not None and step == loop_cfg.fail_at_step:
            raise RuntimeError(f"injected failure at step {step}")
        batch = {k: jnp.asarray(v) for k, v in batch_fn(step).items()}
        if use_manual_dp:
            p, o, e, metrics = step_fn(state["params"], state["opt"],
                                       state["err"], batch)
            state = {"params": p, "opt": o, "err": e}
        else:
            p, o, metrics = step_fn(state["params"], state["opt"], batch)
            state = {"params": p, "opt": o}
        if ckpt is not None and (step + 1) % loop_cfg.ckpt_every == 0:
            ckpt.save(step + 1, state)
        if (step + 1) % loop_cfg.log_every == 0 or step == start_step:
            loss = float(metrics["loss_total"])
            history["step"].append(step + 1)
            history["loss"].append(loss)
            print(f"step {step + 1:5d} loss {loss:.4f} "
                  f"({(time.time() - t0):.1f}s)")
    history["final_state"] = state     # type: ignore
    return history
