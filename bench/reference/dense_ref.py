"""Plain reference of one training step of a dense decoder: forward,
loss, gradients and the AdamW update, in straightforward ``jax.numpy`` at
float32 and ``precision="highest"``, with nothing taken from the program.

The architecture is the Qwen2 decoder (arXiv 2407.10671; Hugging Face
``Qwen2ForCausalLM``), pre-norm blocks: RMSNorm (``rms_norm_eps``,
weight), causal grouped-query attention with biases on the query, key
and value projections (``attention_bias``) and rotary embeddings
(``rope_theta``, rotate-half on the two halves of each head, scores
scaled by ``head_dim ** -0.5``), a SwiGLU MLP
(``silu(x W_gate) * (x W_up) W_down``), a final RMSNorm and an LM head
that is the embedding's transpose where ``tie_word_embeddings``; the
loss is the mean next-token cross-entropy.  Attention here materialises
each row's full score matrix.

The weights are made from the seed by :func:`init`, in the program's
parameter layout, so that the harness hands the program the same values
the reference starts from.  The optimizer is AdamW with a global-norm
gradient clip and decoupled weight decay on every parameter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops as F

HIGHEST = jax.lax.Precision.HIGHEST


def arch(config: dict) -> dict:
    """The model's sizes from a configuration file that holds them under
    the names of its public ``config.json``."""
    return {"n_layers": config["num_hidden_layers"],
            "d_model": config["hidden_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "qkv_bias": config["attention_bias"],
            "tie_embeddings": config["tie_word_embeddings"],
            "rope_theta": config["rope_theta"],
            "norm_eps": config["rms_norm_eps"]}


def key(seed: int):
    """A PRNG key for any seed up to 64 bits."""
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def padded_vocab(model: dict) -> int:
    """Rows of the embedding in the program's layout: the vocabulary
    rounded up to a multiple of 256.  The extra rows are zero, and no
    token or label reaches them."""
    return -(-model["vocab_size"] // 256) * 256


def init(model: dict, k, dtype=jnp.float32) -> dict:
    """Weights in the program's layout: normal with standard deviation
    ``fan_in ** -0.5`` for every matrix (the embedding's fan-in is the
    vocabulary), ones for the norms, zeros for the biases and for the
    embedding's padding rows.  Layers are stacked on axis 0."""
    L, d, v = model["n_layers"], model["d_model"], model["vocab_size"]
    h, hd, ff = model["n_heads"], F.head_dim(model), model["d_ff"]
    kv = model.get("n_kv_heads", h)
    vp = padded_vocab(model)
    ks = jax.random.split(k, 9)

    def normal(kk, shape, fan_in):
        return (jax.random.normal(kk, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    real = (jnp.arange(vp) < v).astype(dtype)
    params = {
        "tok_embed": normal(ks[0], (vp, d), v) * real[:, None],
        "final_norm": jnp.ones((d,), dtype),
        "layers": {
            "ln1": jnp.ones((L, d), dtype),
            "wq": normal(ks[2], (L, d, h, hd), d),
            "wk": normal(ks[3], (L, d, kv, hd), d),
            "wv": normal(ks[4], (L, d, kv, hd), d),
            "wo": normal(ks[5], (L, h, hd, d), h * hd),
            "ln2": jnp.ones((L, d), dtype),
            "gate": normal(ks[6], (L, d, ff), d),
            "up": normal(ks[7], (L, d, ff), d),
            "down": normal(ks[8], (L, ff, d), ff),
        },
    }
    if not model.get("tie_embeddings"):
        params["lm_head"] = normal(ks[1], (d, vp), d) * real[None, :]
    if model.get("qkv_bias"):
        params["layers"].update(
            bq=jnp.zeros((L, h, hd), dtype), bk=jnp.zeros((L, kv, hd), dtype),
            bv=jnp.zeros((L, kv, hd), dtype))
    return params


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (s, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, eps, theta):
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    zero = jnp.zeros((), x.dtype)
    h = _rms(x, p["ln1"], eps)
    q = _rope(mm("bsd,dhk->bshk", h, p["wq"]) + p.get("bq", zero), theta)
    k = _rope(mm("bsd,dhk->bshk", h, p["wk"]) + p.get("bk", zero), theta)
    v = mm("bsd,dhk->bshk", h, p["wv"]) + p.get("bv", zero)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    s = mm("bqhk,bchk->bhqc", q, k) / np.sqrt(q.shape[-1])
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = mm("bhqc,bchk->bqhk", jax.nn.softmax(s, -1), v)
    x = x + mm("bshk,hkd->bsd", o, p["wo"])
    h = _rms(x, p["ln2"], eps)
    a = mm("bsd,df->bsf", h, p["gate"])
    u = mm("bsd,df->bsf", h, p["up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(a) * u, p["down"])


def nll_sum(params, tokens, labels, shape):
    """Summed next-token cross-entropy of ``tokens`` (rows, seq);
    ``shape`` is ``(vocab_size, rms_norm_eps, rope_theta)``."""
    vocab, eps, theta = shape
    layer = jax.checkpoint(functools.partial(_layer, eps=eps, theta=theta))
    x = params["tok_embed"][tokens]
    x, _ = jax.lax.scan(lambda c, p: (layer(c, p), None), x,
                        params["layers"])
    x = _rms(x, params["final_norm"], eps)
    head = (params["lm_head"] if "lm_head" in params
            else params["tok_embed"].T)[:, :vocab]
    logits = jnp.einsum("bsd,dv->bsv", x, head, precision=HIGHEST)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


_grad_block = jax.jit(jax.value_and_grad(nll_sum), static_argnums=3)
_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t),
                 donate_argnums=0)
_global_norm = jax.jit(lambda t: jnp.sqrt(sum(
    jnp.sum(x * x) for x in jax.tree.leaves(t))))


def loss_and_grad(model: dict, params, batch: dict, rows_per_block: int):
    """Mean loss and its gradient over the batch, ``rows_per_block`` rows
    at a time so that the score matrices fit."""
    tokens, labels = batch["tokens"], batch["labels"]
    shape = (model["vocab_size"], model["norm_eps"], model["rope_theta"])
    n = tokens.size
    total, grads = 0.0, None
    for r in range(0, tokens.shape[0], rows_per_block):
        s, g = _grad_block(params, jnp.asarray(tokens[r:r + rows_per_block]),
                           jnp.asarray(labels[r:r + rows_per_block]), shape)
        total += float(s)
        grads = g if grads is None else _add(grads, g)
        del g
    return total / n, _scale(grads, jnp.float32(1.0 / n))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw_leaf(p, g, m, v, step, scale, opt: dict):
    g = g * scale
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step)) + opt["eps"])
    return p - opt["lr"] * (u + opt["weight_decay"] * p), m, v


def adamw(params, grads, m: list, v: list, step: int, opt: dict):
    """One AdamW step (``step`` counts from 1) after clipping the gradient
    to a global norm of ``opt["grad_clip"]``.  The moments ``m`` and ``v``
    are host arrays, one per leaf, updated in place; each leaf's moments
    visit the device only for its own update, so the reference needs
    room for the weights and the gradient alone.  Returns the new weights
    and the clip's scale."""
    scale = jnp.minimum(1.0, opt["grad_clip"] / (_global_norm(grads) + 1e-9))
    leaves, tree = jax.tree.flatten(params)
    out = []
    for i, (p, g) in enumerate(zip(leaves, jax.tree.leaves(grads))):
        p, mi, vi = _adamw_leaf(p, g, jnp.asarray(m[i]), jnp.asarray(v[i]),
                                jnp.float32(step), scale, opt)
        m[i], v[i] = np.asarray(mi), np.asarray(vi)
        out.append(p)
    return jax.tree.unflatten(tree, out), float(scale)


def _named(tree) -> dict:
    """Leaves by their own key (``wq``, ``tok_embed``, ...): the same names
    whether the layers are stacked or split into pipeline stages."""
    return {str(path[-1].key): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in _named(tree).items()}


@jax.jit
def _diff_norms(a, b):
    b = _named(b)
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                           - b[k].astype(jnp.float32))))
            for k, x in _named(a).items()}


def leaf_norms(tree) -> dict:
    """Each leaf's Frobenius norm, by leaf name, on the host."""
    return {k: float(v) for k, v in _norms(tree).items()}


def diff_norms(a, b) -> dict:
    """The norm of each leaf's difference between ``a`` and ``b`` (the
    same leaves, in any layout whose leaves keep their names)."""
    return {k: float(v) for k, v in _diff_norms(a, b).items()}


def follow(model: dict, opt: dict, seed: int, batches, rows_per_block: int):
    """The reference's first ``len(batches)`` steps from the seed's
    weights: ``(losses, first clipped gradient's leaf norms, leaf norms of
    the parameters' change after the last step)``, norms by leaf name."""
    make = jax.jit(functools.partial(init, model))
    with jax.default_matmul_precision("highest"):
        params = make(key(seed))
        m = [np.zeros(x.shape, np.float32) for x in jax.tree.leaves(params)]
        v = [np.zeros_like(x) for x in m]
        losses, g1 = [], None
        for i, b in enumerate(batches, start=1):
            loss, grads = loss_and_grad(model, params, b, rows_per_block)
            losses.append(loss)
            norms = leaf_norms(grads) if i == 1 else None
            params, scale = adamw(params, grads, m, v, i, opt)
            if i == 1:
                g1 = {k: x * scale for k, x in norms.items()}
            del grads
        dp = diff_norms(params, make(key(seed)))
    return losses, g1, dp
