"""Pipeline parallelism over a 'pipe' mesh axis via shard_map.

Microbatches rotate through the stages with ``lax.ppermute`` (the JAX
analogue of Megatron's P2P stage links — the communication pattern
Pipette's Eq. 5 prices per hop).  Compute follows the GPipe rotation and
relies on remat for the 1F1B memory profile; the arithmetic is identical
to the sequential model, which the tests assert exactly.  The Pipette
(pp, tp, dp) configuration maps onto a ('pipe', 'data', 'model') mesh
built from the SA worker dedication (launch/mesh.py::mesh_from_mapping).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stage_params_split(layer_params, pp: int):
    """Stacked (L, ...) layer params -> (pp, L/pp, ...) stage-major."""
    def split(a):
        l = a.shape[0]
        assert l % pp == 0, f"n_layers {l} must divide pp {pp}"
        return a.reshape(pp, l // pp, *a.shape[1:])
    return jax.tree.map(split, layer_params)


def pipeline_loss_fn(embed_fn: Callable, stage_fn: Callable,
                     head_loss_fn: Callable, mesh: Mesh, *,
                     axis: str = "pipe", remat: bool = True,
                     data_axis: str = ""):
    """Builds loss(params, tokens_mb, labels_mb) running pipeline-parallel.

    params = {"stages": (pp, L/pp, ...) sharded over axis,
              "shared": replicated embed/head/etc}
    tokens_mb, labels_mb: (n_mb, mb, S); with ``data_axis`` set, the mb dim
    is data-parallel-sharded over that axis and the loss is pmean'd.
    """
    pp = mesh.shape[axis]

    def local_fn(stages_local, shared, tokens_mb, labels_mb):
        idx = jax.lax.axis_index(axis)
        n_mb = tokens_mb.shape[0]
        stages_local = jax.tree.map(lambda a: a[0], stages_local)
        sfn = jax.checkpoint(stage_fn) if remat else stage_fn
        # the head's logits are the largest activation of a tick: recompute
        # them in the backward pass instead of keeping one set per tick
        hfn = jax.checkpoint(head_loss_fn) if remat else head_loss_fn
        ticks = n_mb + pp - 1
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def tick(carry, t):
            state, loss_sum = carry
            mb_in = jnp.clip(t, 0, n_mb - 1)
            mb_out = t - (pp - 1)
            # only stage 0 embeds; only the last stage pays the head/loss
            # (lax.cond on the stage index — per-device branching inside
            # shard_map keeps the 15/16 other ranks idle on these)
            x0 = jax.lax.cond(
                idx == 0,
                lambda: embed_fn(shared, tokens_mb[mb_in]).astype(state.dtype),
                lambda: state)
            inp = jnp.where(idx == 0, x0, state)
            out = sfn(stages_local, inp)
            lbl = labels_mb[jnp.clip(mb_out, 0, n_mb - 1)]
            valid = (idx == pp - 1) & (mb_out >= 0) & (mb_out < n_mb)
            mb_loss = jax.lax.cond(
                valid,
                lambda: hfn(shared, out, lbl),
                lambda: jnp.zeros((), jnp.float32))
            loss_sum = loss_sum + mb_loss
            state = jax.lax.ppermute(out, axis, perm)
            return (state, loss_sum), None

        dummy = embed_fn(shared, tokens_mb[0])
        (state, loss_sum), _ = jax.lax.scan(
            tick, (jnp.zeros_like(dummy), jnp.zeros((), jnp.float32)),
            jnp.arange(ticks))
        total = jax.lax.psum(loss_sum, axis)       # only last stage nonzero
        if data_axis:
            total = jax.lax.pmean(total, data_axis)
        return total / n_mb

    batch_spec = P(None, data_axis, None) if data_axis else P()
    wrapped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis), P(), batch_spec, batch_spec),
        out_specs=P(),
        check_vma=False)

    def loss(params, tokens_mb, labels_mb):
        return wrapped(params["stages"], params["shared"], tokens_mb,
                       labels_mb)

    return loss


def model_pipeline_loss(cfg, mesh: Mesh, *, axis: str = "pipe",
                        data_axis: str = ""):
    """:func:`pipeline_loss_fn` over the model stack's own embedding, layer
    scan and loss head, for params laid out by :func:`model_stage_params`.

    Every stage runs ``n_layers / pp`` layers of one uniform stack, so the
    config must be a dense decoder without per-layer attention variants.
    """
    from ..models import model as M
    from ..models.sharding import ShardCtx
    from ..models.transformer import run_stack

    if cfg.family != "dense" or cfg.local_global_period or cfg.sliding_window:
        raise NotImplementedError(
            f"pipeline stages need a uniform dense stack, got {cfg.name}")
    stage_cfg = cfg.replace(n_layers=cfg.n_layers // mesh.shape[axis])

    def embed_fn(shared, tokens):
        return M.embed_inputs(shared, cfg, tokens)[0]

    def stage_fn(stage, x):
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        return run_stack(x, {"layers": stage}, stage_cfg, ShardCtx(),
                         positions)[0]

    def head_loss_fn(shared, x, labels):
        return M.head_loss(shared, cfg, x, labels)

    return pipeline_loss_fn(embed_fn, stage_fn, head_loss_fn, mesh,
                            axis=axis, data_axis=data_axis)


def model_stage_params(params, pp: int):
    """Model params -> ``{"stages": (pp, L/pp, ...) layers, "shared": the
    rest}``, the layout :func:`model_pipeline_loss` takes."""
    shared = {k: v for k, v in params.items() if k != "layers"}
    return {"stages": stage_params_split(params["layers"], pp),
            "shared": shared}
