"""Pipeline parallelism over a 'pipe' mesh axis via shard_map.

Microbatches rotate through the stages with ``lax.ppermute`` (the JAX
analogue of Megatron's P2P stage links — the communication pattern
Pipette's Eq. 5 prices per hop).  Compute follows the GPipe rotation and
relies on remat for the 1F1B memory profile; the arithmetic is identical
to the sequential model, which the tests assert exactly.  The rotation
carries the layers alone: once it ends, the last stage hands each
microbatch's final hidden state to the stage that owns it, and every
stage runs the head and loss for its share of the microbatches, so the
head's cost is spread evenly over the stages, as the planner's profile
prices it.  The Pipette (pp, tp, dp) configuration maps onto a ('pipe',
'data', 'model') mesh built from the SA worker dedication
(launch/mesh.py::mesh_from_mapping).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs


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

    Stage ``j`` runs the head for microbatches ``j, j + pp, j + 2 pp, ...``
    below ``n_mb``: ``ceil(n_mb / pp)`` slots.  Each lowering fires the
    ``launch.pipeline_head`` layout event with ``slots`` and ``n_mb``.
    """
    pp = mesh.shape[axis]

    def local_fn(stages_local, shared, tokens_mb, labels_mb):
        idx = jax.lax.axis_index(axis)
        n_mb = tokens_mb.shape[0]
        stages_local = jax.tree.map(lambda a: a[0], stages_local)
        sfn = jax.checkpoint(stage_fn) if remat else stage_fn
        # the head's logits are the largest activation of a step: recompute
        # them in the backward pass instead of keeping one set per slot
        hfn = jax.checkpoint(head_loss_fn) if remat else head_loss_fn
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def tick(state, t):
            # only stage 0 embeds (lax.cond on the stage index: the other
            # ranks skip it); every stage runs its layers and hops
            inp = jax.lax.cond(
                idx == 0,
                lambda: embed_fn(
                    shared, tokens_mb[jnp.clip(t, 0, n_mb - 1)]
                ).astype(state.dtype),
                lambda: state)
            out = sfn(stages_local, inp)
            return jax.lax.ppermute(out, axis, perm), out

        dummy = embed_fn(shared, tokens_mb[0])
        _, outs = jax.lax.scan(tick, jnp.zeros_like(dummy),
                               jnp.arange(n_mb + pp - 1))
        # on the last stage, ticks pp-1 .. pp-2+n_mb hold the final hidden
        # states of microbatches 0 .. n_mb-1
        final = outs[pp - 1:]
        loss_sum = jnp.zeros((), jnp.float32)
        for base in range(0, n_mb, pp):
            # slot ``base // pp``: microbatch base + j goes to stage j, the
            # last stage keeps its own; a stage past n_mb - 1 gets nothing
            own = final[min(base + pp - 1, n_mb - 1)]
            h = jnp.where(idx == pp - 1, own, 0)
            for j in range(min(pp - 1, n_mb - base)):
                h = h + jax.lax.ppermute(final[base + j], axis,
                                         [(pp - 1, j)])
            k = base + idx
            loss_sum = loss_sum + jax.lax.cond(
                k < n_mb,
                lambda: hfn(shared, h, labels_mb[jnp.minimum(k, n_mb - 1)]),
                lambda: jnp.zeros((), jnp.float32))
        total = jax.lax.psum(loss_sum, axis)
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
        n_mb = tokens_mb.shape[0]
        obs.count_layout("launch.pipeline_head",
                         slots=-(-n_mb // pp), n_mb=n_mb)
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
