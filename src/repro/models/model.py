"""Top-level model API: train forward/loss, prefill, decode.

Decode uses an unrolled per-layer loop so heterogeneous caches stay exact:
full KV rows for global-attention layers, ring buffers for sliding-window
layers (gemma3 locals), SSM state + conv tails for mamba layers, and the
weight-tied shared-attention rows of hybrid archs.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import mamba as mam
from .attention import decode_attention
from .config import ModelConfig
from .layers import rms_norm
from .moe import moe_block
from .sharding import ShardCtx
from .transformer import (_proj_qkv, attn_block, init_params, layer_plan,
                          mlp_block, run_stack)

__all__ = ["init_params", "forward_logits", "loss_fn", "head_loss",
           "embed_inputs", "prefill",
           "init_cache", "decode_step", "cache_pspecs"]


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_inputs(params, cfg: ModelConfig, tokens, img_embeds=None):
    x = params["tok_embed"][tokens]                     # (b, s_text, d)
    if img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def _head(params, cfg: ModelConfig):
    if cfg.tie_embeddings or "lm_head" not in params:
        return params["tok_embed"].T
    return params["lm_head"]


def _project_logits(x, params, cfg: ModelConfig):
    """Final projection with phantom-row masking (padded_vocab is exact)."""
    logits = x.astype(jnp.bfloat16) @ _head(params, cfg)
    if cfg.padded_vocab != cfg.vocab_size:
        bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                         0.0, -1e30).astype(logits.dtype)
        logits = logits + bias
    return logits


def forward_logits(params, cfg: ModelConfig, ctx: ShardCtx, tokens,
                   img_embeds=None):
    x, positions = embed_inputs(params, cfg, tokens, img_embeds)
    x, _ = run_stack(x, params, cfg, ctx, positions)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _project_logits(x, params, cfg)


def loss_fn(params, cfg: ModelConfig, ctx: ShardCtx, batch) -> Tuple[jax.Array, Dict]:
    """Mean next-token CE over valid labels (labels < 0 are masked)."""
    logits = forward_logits(params, cfg, ctx, batch["tokens"],
                            batch.get("img_embeds"))
    loss, n = cross_entropy(logits, batch["labels"], cfg)
    return loss, {"loss": loss, "tokens": n}


def head_loss(params, cfg: ModelConfig, x, labels) -> jax.Array:
    """Final norm, vocab projection and mean CE of a stack's output ``x``
    (what each pipeline stage runs for the microbatches it owns)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return cross_entropy(_project_logits(x, params, cfg), labels, cfg)[0]


def cross_entropy(logits, labels, cfg: ModelConfig):
    """``(mean CE over labels >= 0, count of those labels)``."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    safe = jnp.maximum(labels, 0)
    onehot = jax.nn.one_hot(safe, cfg.padded_vocab, dtype=jnp.float32)
    picked = jnp.einsum("bsv,bsv->bs", lf, onehot)
    nll = lse - picked
    mask = (labels >= 0).astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / n, n


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, ctx: ShardCtx, tokens, img_embeds=None):
    """Full-sequence pass that returns (last_token_logits, cache)."""
    x, positions = embed_inputs(params, cfg, tokens, img_embeds)
    x, raw = run_stack(x, params, cfg, ctx, positions, collect_cache=True)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = _project_logits(x, params, cfg)

    plan, meta = layer_plan(cfg)
    cache: Dict[str, Any] = {}
    if cfg.family in ("ssm", "hybrid"):
        if cfg.hybrid_attn_period:
            (h, conv_tail), shared_kv = raw
            if shared_kv:
                cache["k"], cache["v"] = shared_kv
        else:
            h, conv_tail = raw
        cache["ssm"] = h
        cache["conv"] = conv_tail
    else:
        k, v = raw                                       # (L, b, S, KV, hd)
        full_rows = [i for i, e in enumerate(plan) if e["cache"][0] == "full"]
        ring_rows = [(i, e["cache"][2]) for i, e in enumerate(plan)
                     if e["cache"][0] == "ring"]
        if full_rows:
            idx = np.array(full_rows)
            cache["k"], cache["v"] = k[idx], v[idx]
        if ring_rows:
            w = ring_rows[0][1]
            idx = np.array([i for i, _ in ring_rows])
            s = k.shape[2]
            assert s % w == 0, "prefill length must be a multiple of the window"
            cache["k_ring"], cache["v_ring"] = k[idx, :, -w:], v[idx, :, -w:]
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    plan, meta = layer_plan(cfg)
    kv, hd = cfg.n_kv_heads, cfg.hd
    cache: Dict[str, Any] = {}
    n_full = meta["full"] + len(meta["shared_at"])
    if n_full:
        cache["k"] = jnp.zeros((n_full, batch, seq_len, kv, hd), dtype)
        cache["v"] = jnp.zeros((n_full, batch, seq_len, kv, hd), dtype)
    if meta["ring"]:
        w = next((e["cache"][2] for e in plan
                  if e.get("cache", ("",))[0] == "ring"), 0)
        cache["k_ring"] = jnp.zeros((meta["ring"], batch, w, kv, hd), dtype)
        cache["v_ring"] = jnp.zeros((meta["ring"], batch, w, kv, hd), dtype)
    if meta["ssm"]:
        if cfg.ssm_variant == "mamba2":
            cache["ssm"] = jnp.zeros((meta["ssm"], batch, cfg.n_ssm_heads,
                                      cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
            conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        else:
            cache["ssm"] = jnp.zeros((meta["ssm"], batch, cfg.d_inner,
                                      cfg.ssm_state), jnp.float32)
            conv_dim = cfg.d_inner
        cache["conv"] = jnp.zeros((meta["ssm"], batch, cfg.ssm_conv - 1,
                                   conv_dim), dtype)
    return cache


def cache_pspecs(cfg: ModelConfig, ctx: ShardCtx, batch: int) -> Dict[str, P]:
    """Sharding for the decode cache: batch over dp when divisible, the
    sequence dim of full KV rows over the model axis (flash-decoding
    combine), SSM channels over the model axis."""
    dp = ctx.dp if ctx.dp else None
    nd = 1
    for a in (ctx.dp or ()):
        nd *= ctx.n(a)
    bspec = dp if (batch % max(nd, 1) == 0 and nd > 1) else None
    seq_axes = ctx.tp if bspec is not None else (ctx.tp,) + tuple(ctx.dp)
    specs = {}
    specs["k"] = P(None, bspec, seq_axes, None, None)
    specs["v"] = specs["k"]
    specs["k_ring"] = P(None, bspec, None, None, None)
    specs["v_ring"] = specs["k_ring"]
    if cfg.ssm_variant == "mamba2":
        specs["ssm"] = P(None, bspec, ctx.tp, None, None)
    else:
        specs["ssm"] = P(None, bspec, ctx.tp, None)
    specs["conv"] = P(None, bspec, None, ctx.tp)
    return specs


def _decode_attn(x, lp, cfg, ctx, cache, entry, pos, shared_row=None):
    """One attention layer decode step; returns (out, cache updates)."""
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None], (b, 1)).astype(jnp.int32)
    q, k, v = _proj_qkv(x, lp, cfg, positions, entry["theta"])
    kind, *rest = entry["cache"]
    if kind == "full":
        row = rest[0] if shared_row is None else shared_row
        ck, cv = cache["k"][row], cache["v"][row]
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, pos, 0, 0))
        o = decode_attention(q, ck, cv, pos)
        upd = {"k": (row, ck), "v": (row, cv)}
    else:
        row, w = rest
        slot = jnp.mod(pos, w)
        ck, cv = cache["k_ring"][row], cache["v_ring"][row]
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
        o = decode_attention(q, ck, cv, jnp.minimum(pos, w - 1))
        upd = {"k_ring": (row, ck), "v_ring": (row, cv)}
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), upd


def _segments(plan, shared_at=()):
    """Group consecutive layers with identical (kind, cache-kind, window,
    theta) into scannable segments, breaking after shared-attention
    application points.  Returns [(sig, [indices], entry)]."""
    segs = []
    breaks = set(shared_at)
    prev_broke = True
    for i, e in enumerate(plan):
        sig = (e["kind"], e.get("cache", ("ssm",))[0],
               e.get("cache", (None, None, 0))[2]
               if e.get("cache", ("", 0))[0] == "ring" else 0,
               e["theta"])
        if segs and segs[-1][0] == sig and not prev_broke:
            segs[-1][1].append(i)
        else:
            segs.append((sig, [i], e))
        prev_broke = i in breaks
    return segs


def _decode_layer_body(x, lp, ck, cv, cfg, ctx, pos, *, kind, cache_kind,
                       window, theta):
    """One decode layer (works per-row inside a scan).  Returns
    (x, new_ck, new_cv)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None], (b, 1)).astype(jnp.int32)
    q, k, v = _proj_qkv(h, lp, cfg, positions, theta)
    if cache_kind == "full":
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, pos, 0, 0))
        o = decode_attention(q, ck, cv, pos)
    else:
        w = window
        slot = jnp.mod(pos, w)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, slot, 0, 0))
        o = decode_attention(q, ck, cv, jnp.minimum(pos, w - 1))
    x = x + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if kind == "moe":
        moe_p = {"router": lp["router"], "gate": lp["e_gate"],
                 "up": lp["e_up"], "down": lp["e_down"]}
        m = moe_block(h, moe_p, k=cfg.experts_per_token,
                      n_experts=cfg.n_experts,
                      capacity_factor=cfg.capacity_factor,
                      mesh=ctx.mesh, data_axes=ctx.dp,
                      model_axis=ctx.tp, fsdp=False)
    else:
        m = mlp_block(h, lp)
    return x + m, ck, cv


def decode_step(params, cfg: ModelConfig, ctx: ShardCtx, token, cache, pos):
    """token: (b, 1) int32; pos: scalar int32.  Returns (logits, cache).

    Lowered as one lax.scan per homogeneous layer segment (dense archs:
    a single scan; gemma3: alternating local/global segments; hybrids:
    mamba segments + unrolled weight-tied shared attention) so decode
    compiles stay small at 512-way SPMD."""
    plan, meta = layer_plan(cfg)
    x = params["tok_embed"][token]                      # (b, 1, d)
    new_cache = dict(cache)
    shared_seen = 0

    for sig, idxs, entry in _segments(plan, meta["shared_at"]):
        kind, cache_kind, window, theta = sig
        i0, i1 = idxs[0], idxs[-1] + 1
        seg_params = jax.tree.map(lambda a: a[i0:i1], params["layers"])
        if kind in ("attn", "moe"):
            ckey, vkey = ("k", "v") if cache_kind == "full" else \
                ("k_ring", "v_ring")
            r0 = plan[i0]["cache"][1]
            r1 = r0 + len(idxs)

            def body(xc, xs):
                lp, ck, cv = xs
                xc, ck, cv = _decode_layer_body(
                    xc, lp, ck, cv, cfg, ctx, pos, kind=kind,
                    cache_kind=cache_kind, window=window, theta=theta)
                return xc, (ck, cv)

            x, (cks, cvs) = jax.lax.scan(
                body, x, (seg_params, new_cache[ckey][r0:r1],
                          new_cache[vkey][r0:r1]))
            new_cache[ckey] = new_cache[ckey].at[r0:r1].set(cks)
            new_cache[vkey] = new_cache[vkey].at[r0:r1].set(cvs)
        else:                                            # mamba segment
            r0 = plan[i0]["ssm_row"]
            r1 = r0 + len(idxs)
            blk = mam.mamba2_block if kind == "mamba2" else mam.mamba1_block

            def mbody(xc, xs):
                lp, hs, cc = xs
                h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
                y, (hs2, cc2) = blk(h[:, 0], lp, cfg, h0=hs, conv0=cc,
                                    single_step=True)
                return xc + y[:, None], (hs2, cc2.astype(cc.dtype))

            x, (hss, ccs) = jax.lax.scan(
                mbody, x, (seg_params, new_cache["ssm"][r0:r1],
                           new_cache["conv"][r0:r1]))
            new_cache["ssm"] = new_cache["ssm"].at[r0:r1].set(hss)
            new_cache["conv"] = new_cache["conv"].at[r0:r1].set(ccs)

        # hybrid: weight-tied shared attention after every k-th layer
        if (i1 - 1) in meta["shared_at"]:
            sh = params["shared"]
            hh = rms_norm(x, sh["ln1"], cfg.norm_eps)
            entry_s = {"theta": cfg.rope_theta,
                       "cache": ("full", meta["full"] + shared_seen)}
            a, upd = _decode_attn(hh, sh, cfg, ctx, new_cache, entry_s, pos)
            for key, (row, arr) in upd.items():
                new_cache[key] = jax.lax.dynamic_update_slice(
                    new_cache[key], arr[None], (row,) + (0,) * arr.ndim)
            x = x + a
            hh = rms_norm(x, sh["ln2"], cfg.norm_eps)
            x = x + mlp_block(hh, sh)
            shared_seen += 1

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _project_logits(x, params, cfg)
    return logits[:, 0], new_cache
