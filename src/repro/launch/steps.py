"""jit-able train / prefill / decode steps used by the launcher, the
dry-run and the examples."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..models import model as M
from ..models.config import ModelConfig
from ..models.sharding import ShardCtx
from ..optim.adamw import AdamW, AdamWState


def make_train_step(cfg: ModelConfig, ctx: ShardCtx, opt: AdamW,
                    n_micro: int = 1):
    """Microbatch-accumulation training step (Pipette's bs_micro knob).

    grads accumulate in fp32 across n_micro microbatches (each fwd+bwd
    under remat), then one AdamW update.  The microbatch loop is unrolled:
    as a while loop its fp32 accumulator carry is double-buffered, which
    for 12-layer gpt-1.1b on one 16 GB TPU v5e costs 1.4 GiB of the
    15.75 GiB the step may use."""

    def train_step(params, opt_state: AdamWState, batch: Dict[str, Any]):
        def micro_loss(p, mb):
            return M.loss_fn(p, cfg, ctx, mb)

        if n_micro == 1:
            (loss, aux), grads = jax.value_and_grad(micro_loss, has_aux=True)(
                params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:]),
                batch)

            def micro_step(carry, mb):
                gacc, lacc = carry
                (loss, _), g = jax.value_and_grad(micro_loss, has_aux=True)(
                    params, mb)
                gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                    gacc, g)
                return (gacc, lacc + loss), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            (gsum, lsum), _ = jax.lax.scan(micro_step, (zeros, 0.0), mbs,
                                           unroll=True)
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro

        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, {"loss": loss}

    return train_step


def make_prefill_step(cfg: ModelConfig, ctx: ShardCtx):
    def prefill_step(params, batch: Dict[str, Any]):
        logits, cache = M.prefill(params, cfg, ctx, batch["tokens"],
                                  batch.get("img_embeds"))
        return logits, cache
    return prefill_step


def make_decode_step(cfg: ModelConfig, ctx: ShardCtx):
    def serve_step(params, cache, token, pos):
        logits, cache = M.decode_step(params, cfg, ctx, token, cache, pos)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, logits, cache
    return serve_step
