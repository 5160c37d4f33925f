"""Chunked flash-pattern attention in pure jnp.

This is the attention the model stack runs on every backend, TPU
included, and the numerically-stable oracle of the Pallas flash-attention
kernel in ``repro.kernels`` (which the model stack does not call).  The
online-softmax recurrence keeps HLO bytes realistic — no (Sq, Sk) score
matrix is ever materialised beyond a (chunk_q, chunk_k) tile, exactly
like the kernel; the backward pass recomputes each tile rather than
keeping all of them.

Supports GQA (n_kv_heads <= n_heads), causal masking, sliding windows
(gemma3 local layers) and offset queries (continuation / decode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pick_chunk(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is <= target (>=1)."""
    c = min(s, target)
    while s % c:
        c -= 1
    return c


def chunked_attention(
    q: jax.Array,                 # (b, Sq, H, hd)
    k: jax.Array,                 # (b, Sk, KV, hd)
    v: jax.Array,                 # (b, Sk, KV, hd)
    *,
    causal: bool = True,
    window: int = 0,              # 0 = unbounded; may be a traced scalar
    q_offset: int = 0,            # absolute position of q[0]
    chunk_q: int = 512,
    chunk_k: int = 1024,
    min_q_blocks: int = 1,        # ensure nq % this == 0 (seq sharding)
    block_constrain=None,         # fn(x, block_dim) -> x; shards the q-block dim
) -> jax.Array:
    """Flash-pattern attention, q-block-parallel (vmap) over the outer dim.

    The q-block axis is a real batch dim, so it can be sharded (sequence /
    context parallelism) — the default for archs whose head count does not
    divide the model axis (granite 24H, qwen1.5 20H; DESIGN.md §4)."""
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    scale = 1.0 / (hd ** 0.5)

    cq = _pick_chunk(sq, chunk_q)
    if min_q_blocks > 1:
        while cq > 1 and (sq // cq) % min_q_blocks:
            cq -= 1
        cq = _pick_chunk(sq, cq)
    ck = _pick_chunk(sk, chunk_k)
    nq, nk = sq // cq, sk // ck

    qc = q.reshape(b, nq, cq, kv, g, hd).astype(jnp.float32) * scale
    if block_constrain is not None:
        qc = block_constrain(qc, 1)
    kc = k.reshape(b, nk, ck, kv, hd).astype(jnp.float32).swapaxes(0, 1)
    vc = v.reshape(b, nk, ck, kv, hd).astype(jnp.float32).swapaxes(0, 1)

    q_pos_all = q_offset + jnp.arange(sq, dtype=jnp.int32).reshape(nq, cq)
    k_pos_all = jnp.arange(sk, dtype=jnp.int32).reshape(nk, ck)
    win = jnp.asarray(window, jnp.int32)

    def q_block(q_blk, q_pos):
        # q_blk: (b, cq, kv, g, hd); q_pos: (cq,)
        def kv_step(carry, xs):
            m, l, acc = carry
            k_blk, v_blk, k_pos = xs
            s = jnp.einsum("bqkgd,bckd->bkgqc", q_blk, k_blk)
            delta = q_pos[:, None] - k_pos[None, :]
            ok = jnp.full(delta.shape, True)
            if causal:
                ok &= delta >= 0
            ok &= (win <= 0) | (delta < win)
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqc,bckd->bkgqd", p, v_blk)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        # recompute each tile's scores in the backward pass instead of
        # keeping all nk of them (a 4-sequence microbatch of 12-layer
        # gpt-1.1b at seq 2048 needed 8.7 GiB of temporaries, 2.9 with this)
        kv_step = jax.checkpoint(kv_step)
        m0 = jnp.full((b, kv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, kv, g, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kc, vc, k_pos_all))
        # rows with no allowed key (padded windows / negative offsets) -> 0
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.where(m[..., None] <= NEG_INF * 0.5, 0.0, out)

    outs = jax.vmap(q_block, in_axes=(1, 0), out_axes=1)(qc, q_pos_all)
    # outs: (b, nq, kv, g, cq, hd)
    if block_constrain is not None:
        outs = block_constrain(outs, 1)
    out = outs.transpose(0, 1, 4, 2, 3, 5).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,                 # (b, 1, H, hd)
    k_cache: jax.Array,           # (b, S, KV, hd)
    v_cache: jax.Array,           # (b, S, KV, hd)
    pos,                          # scalar int32: index of the current token
    *,
    window: int = 0,
) -> jax.Array:
    """Single-token attention against a (possibly sharded) KV cache.

    The reductions over S lower to all-reduces when the cache's sequence
    dimension is sharded — flash-decoding's partial-softmax combine, done
    by GSPMD.
    """
    b, _, h, hd = q.shape
    _, s, kvh, _ = k_cache.shape
    g = h // kvh
    scale = 1.0 / (hd ** 0.5)

    qf = q.reshape(b, kvh, g, hd).astype(jnp.float32) * scale
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    sc = jnp.einsum("bkgd,bskd->bkgs", qf, kf)
    k_pos = jnp.arange(s, dtype=jnp.int32)
    ok = k_pos <= pos
    win = jnp.asarray(window, jnp.int32)
    ok &= (win <= 0) | (pos - k_pos < win)
    sc = jnp.where(ok[None, None, None], sc, NEG_INF)
    m = sc.max(axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf) / p.sum(-1, keepdims=True)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def reference_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """O(S^2)-memory oracle used only in tests."""
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    qf = q.reshape(b, sq, kv, g, hd).astype(jnp.float32) / (hd ** 0.5)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qf, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    delta = q_pos[:, None] - k_pos[None, :]
    ok = jnp.full(delta.shape, True)
    if causal:
        ok &= delta >= 0
    if window and window > 0:
        ok &= delta < window
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqc,bckd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)
