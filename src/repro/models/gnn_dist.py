"""Explicit 1D-distributed message passing for full-batch-large graphs.

Why this exists: expressing node sharding through sharding *constraints*
cannot tell XLA that scatter destinations are block-local, so the
partitioner replicates node state and the [N, d] scatter buffers
(meshgraphnet on ogb_products peaks at 151 GiB/device with replicated
nodes; constraint-based sharding measured 455 GiB — EXPERIMENTS.md §Perf).
Under ``shard_map`` the layout is explicit — the same philosophy as BENU's
DistributedRowStore: partition the state, move *requests*, never replicate.

Layout (mesh axes ("data", "model"); multi-pod adds "pod" to the edge axes):
    node tensors   block-partitioned over "model": [N/S, d] per device,
                   replicated across "data"
    edge tensors   partitioned over "data"(x"pod"): [E/D] per device,
                   replicated across "model"

Per layer each device:
    1. ``all_gather`` node blocks over "model"  -> h_full [N, d]
    2. gather h_full[src] for the local edge shard, compute messages
    3. scatter-add into a transient [N, d] partial
    4. ``psum_scatter`` over "model" + ``psum`` over "data"
       -> aggregated node block [N/S, d]
    (max/min aggregations: ``pmax``/``pmin`` over "data" + local slice)

Wire per device per layer ~ 2 x N x d x (S-1)/S bytes — independent of the
edge count (edges never move): the GNN analogue of "shuffle the graph, not
the matches". Gradients flow through the collectives by transposition
(all_gather <-> psum_scatter), so one ``jax.value_and_grad`` over the
shard_mapped loss trains the model.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..layers.mlp import mlp_apply
from .gnn import GNNConfig, _ln


def _make_sum_block(n_shards: int):
    """[N, d] per-device edge-shard partial sums -> node block [N/S, d].

    Edges are sharded over EVERY mesh axis (each device owns a distinct
    shard), so partials differ device-to-device: reduce-scatter over the
    node axis (sums across its group AND splits rows into blocks), then
    psum across the remaining edge axes.
    """
    def sum_block(partial_full: jax.Array, naxis: str,
                  rest_axes) -> jax.Array:
        blk = jax.lax.psum_scatter(partial_full, naxis,
                                   scatter_dimension=0, tiled=True)
        if rest_axes:
            blk = jax.lax.psum(blk, rest_axes)
        return blk

    return sum_block


def _diff_preduce(axis, op: str):
    """Differentiable pmax/pmin: subgradient routed to the extremal
    contributors (ties share; standard max-pool VJP semantics)."""
    red = jax.lax.pmax if op == "max" else jax.lax.pmin

    @jax.custom_vjp
    def f(x):
        return red(x, axis)

    def fwd(x):
        y = red(x, axis)
        return y, (x, y)

    def bwd(res, g):
        x, y = res
        return (jnp.where(x == y, g, 0.0),)

    f.defvjp(fwd, bwd)
    return f


def _make_minmax_block(n_shards: int):
    def minmax_block(partial_full: jax.Array, naxis: str, all_axes,
                     op: str) -> jax.Array:
        full = _diff_preduce(all_axes, op)(partial_full)
        nloc = full.shape[0] // n_shards
        idx = jax.lax.axis_index(naxis)
        return jax.lax.dynamic_slice_in_dim(full, idx * nloc, nloc, axis=0)

    return minmax_block


def _scatter(msg: jax.Array, dst: jax.Array, n: int) -> jax.Array:
    return jax.ops.segment_sum(msg, jnp.clip(dst, 0, n),
                               num_segments=n + 1)[:n]


def _scatter_max(msg, dst, n, emask, big):
    m = jnp.where(emask[:, None], msg, -big)
    out = jax.ops.segment_max(m, jnp.clip(dst, 0, n),
                              num_segments=n + 1)[:n]
    return jnp.maximum(out, -big)


def _scatter_min(msg, dst, n, emask, big):
    m = jnp.where(emask[:, None], msg, big)
    out = jax.ops.segment_min(m, jnp.clip(dst, 0, n),
                              num_segments=n + 1)[:n]
    return jnp.minimum(out, big)


def build_dist_loss(cfg: GNNConfig, mesh: Mesh, n_total: int,
                    naxis: str = "model",
                    edge_axes: Tuple[str, ...] = ("data", "model")):
    """Returns ``(loss_fn, batch_spec_for)`` (shard_mapped).

    batch: node leaves sharded P(naxis) (replicated across the other
    axes), edge leaves sharded over the FLATTENED ``edge_axes``; params
    replicated.
    """
    BIG = 1e30
    assert naxis in edge_axes, "node axis must be one of the edge axes"
    rest = tuple(a for a in edge_axes if a != naxis)
    _minmax_block = _make_minmax_block(mesh.shape[naxis])
    _sum_block = _make_sum_block(mesh.shape[naxis])
    eaxis = edge_axes  # kept name for the closures below

    def local(params, batch):
        e_src, e_dst = batch["edge_src"], batch["edge_dst"]
        emask = e_src < n_total
        h_blk = mlp_apply(params["enc"], batch["x"].astype(cfg.dtype),
                          act=jax.nn.relu, final_act=True)
        h_blk = h_blk * batch["node_mask"][:, None].astype(h_blk.dtype)
        x_blk = (batch["pos"].astype(cfg.dtype)
                 if cfg.kind == "egnn" else None)

        def gathered(t_blk):
            full = jax.lax.all_gather(t_blk, naxis, tiled=True)
            return jnp.concatenate(
                [full, jnp.zeros((1,) + full.shape[1:], full.dtype)],
                axis=0)

        # in-degree per node block (constant across layers)
        deg_partial = _scatter(emask[:, None].astype(jnp.float32),
                               e_dst, n_total)
        deg_blk = jnp.maximum(_sum_block(deg_partial, naxis, rest),
                              1.0)[:, 0]                       # [N/S]

        def mp_layer(lp, h_blk, e_feat, x_blk):
            hp = gathered(h_blk)
            hs = hp[jnp.clip(e_src, 0, n_total)]
            hd = hp[jnp.clip(e_dst, 0, n_total)]
            x_new = x_blk
            if cfg.kind == "mgn":
                e_new = _ln(lp["edge_ln"], mlp_apply(
                    lp["edge_mlp"],
                    jnp.concatenate([e_feat, hs, hd], axis=-1),
                    act=jax.nn.relu)) + e_feat
                e_new = jnp.where(emask[:, None], e_new, 0.0)
                agg = _sum_block(_scatter(e_new, e_dst, n_total),
                                 naxis, rest)
                h_new = _ln(lp["node_ln"], mlp_apply(
                    lp["node_mlp"],
                    jnp.concatenate([h_blk, agg], axis=-1),
                    act=jax.nn.relu)) + h_blk
                return h_new.astype(cfg.dtype), e_new.astype(cfg.dtype), \
                    x_new
            if cfg.kind == "gin":
                msg = jnp.where(emask[:, None], hs, 0.0)
                agg = _sum_block(_scatter(msg, e_dst, n_total),
                                 naxis, rest)
                h_new = _ln(lp["ln"], mlp_apply(
                    lp["mlp"], (1.0 + lp["eps"]) * h_blk + agg,
                    act=jax.nn.relu, final_act=True))
                return h_new.astype(cfg.dtype), e_feat, x_new
            if cfg.kind == "pna":
                m = mlp_apply(lp["pre"],
                              jnp.concatenate([hs, hd], axis=-1))
                m = jnp.where(emask[:, None], m, 0.0)
                s_sum = _sum_block(_scatter(m, e_dst, n_total),
                                   naxis, rest)
                mean = (s_sum / deg_blk[:, None]).astype(cfg.dtype)
                mx = _minmax_block(
                    _scatter_max(m, e_dst, n_total, emask, BIG),
                    naxis, edge_axes, "max")
                mn = _minmax_block(
                    _scatter_min(m, e_dst, n_total, emask, BIG),
                    naxis, edge_axes, "min")
                mx = jnp.where(mx <= -BIG / 2, 0.0, mx).astype(cfg.dtype)
                mn = jnp.where(mn >= BIG / 2, 0.0, mn).astype(cfg.dtype)
                sq = _sum_block(_scatter(m * m, e_dst, n_total),
                                naxis, rest) / deg_blk[:, None]
                std = jnp.sqrt(
                    jnp.maximum(sq - mean.astype(jnp.float32) ** 2, 0.0)
                    + 1e-8).astype(cfg.dtype)
                logd = jnp.log(deg_blk + 1.0)[:, None].astype(cfg.dtype)
                scaled = []
                for a in (mean, mx, mn, std):
                    scaled += [a, a * logd / 2.0, a * 2.0 / logd]
                h_new = h_blk + mlp_apply(
                    lp["post"],
                    jnp.concatenate([h_blk] + scaled, axis=-1)
                    ).astype(cfg.dtype)
                return h_new.astype(cfg.dtype), e_feat, x_new
            if cfg.kind == "egnn":
                xp = gathered(x_blk)
                xs = xp[jnp.clip(e_src, 0, n_total)]
                xd = xp[jnp.clip(e_dst, 0, n_total)]
                diff = xd - xs
                r2 = jnp.sum(diff * diff, axis=-1, keepdims=True)
                m = mlp_apply(lp["phi_e"],
                              jnp.concatenate([hd, hs, r2], axis=-1),
                              act=jax.nn.silu, final_act=True)
                m = jnp.where(emask[:, None], m, 0.0)
                w = mlp_apply(lp["phi_x"], m, act=jax.nn.silu)
                xagg = _sum_block(_scatter(diff * w, e_dst, n_total),
                                  naxis, rest)
                x_new = (x_blk + xagg / deg_blk[:, None]
                         ).astype(cfg.dtype)
                agg = _sum_block(_scatter(m, e_dst, n_total),
                                 naxis, rest)
                h_new = h_blk + mlp_apply(
                    lp["phi_h"],
                    jnp.concatenate([h_blk, agg], axis=-1),
                    act=jax.nn.silu)
                return h_new.astype(cfg.dtype), e_feat, x_new
            raise ValueError(cfg.kind)

        if cfg.kind == "mgn":
            ef = mlp_apply(params["enc_e"],
                           batch["edge_attr"].astype(cfg.dtype),
                           act=jax.nn.relu, final_act=True)
            ef = jnp.where(emask[:, None], ef, 0.0)
        else:
            ef = jnp.zeros((e_src.shape[0], 1), cfg.dtype)
        if x_blk is None:
            x_blk = jnp.zeros((h_blk.shape[0], 1), cfg.dtype)

        def body(carry, lp):
            hh, ee, xx = carry
            hh, ee, xx = mp_layer(lp, hh, ee, xx)
            return (hh, ee, xx), None

        if cfg.remat:
            body = jax.checkpoint(body)
        (h_blk, _, _), _ = jax.lax.scan(body, (h_blk, ef, x_blk),
                                        params["layers"])
        out = mlp_apply(params["dec"], h_blk)
        mask = batch["loss_mask"].astype(jnp.float32)
        if cfg.task == "node_reg":
            num = jnp.sum(((out.astype(jnp.float32)
                            - batch["targets"]) ** 2) * mask[:, None])
        else:
            logits = out.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, batch["labels"][..., None],
                                     axis=-1)[..., 0]
            num = jnp.sum((lse - ll) * mask)
        den = jnp.maximum(jax.lax.psum(jnp.sum(mask), naxis), 1.0)
        loss = jax.lax.psum(num, naxis) / den
        return loss, {"loss": loss}

    node_spec = P(naxis)
    edge_spec = P(eaxis)

    def batch_spec_for(name: str, ndim: int) -> P:
        if name.startswith("edge"):
            return P(edge_axes, *([None] * (ndim - 1)))
        return P(naxis, *([None] * (ndim - 1)))

    def loss_fn(params, batch):
        rep = jax.tree.map(lambda _: P(), params)
        bspecs = {k: batch_spec_for(k, v.ndim) for k, v in batch.items()}
        fn = shard_map(local, mesh=mesh, in_specs=(rep, bspecs),
                       out_specs=(P(), {"loss": P()}),
                       check_vma=False)
        return fn(params, batch)

    return loss_fn, batch_spec_for
