"""Plain reference of :mod:`dense_ref`'s training step for a model too
large for one chip: the same Qwen2 decoder, loss, gradients and AdamW
update (:func:`dense_ref._layer`, :func:`dense_ref._rms`,
:func:`dense_ref._adamw_leaf`), in plain float32 ``jax.numpy`` at
``precision="highest"``, with the state placed by hand.

Each layer's weights, gradient and Adam moments live whole on one device
(``ceil(n_layers / devices)`` layers a device, in order); the embedding,
the final norm and the tied head, with theirs, on the first.  The work
runs on the first device, one layer after another: a layer's weights are
brought to it for its turn in the forward pass, and again in the
backward pass, which runs each layer's ``jax.vjp`` from the layer's
input kept on its home device (recompute), last layer first.  So every
function compiles once, for one device, however many devices hold the
state; and the loops wait for each layer (each part, in the update), so
that the copies brought to the first device do not pile up ahead of the
work there.  Arrays move between devices by ``jax.device_put`` alone: there
is no mesh, no ``shard_map`` and no GSPMD, and nothing of the program's
sharding is shared.

Leaf norms are reported under :func:`dense_ref._named`'s names, each the
root of the squares summed over the layers: the norm of the stacked leaf
that the program holds.  Given the program's first clipped gradient
whole, on the host, :func:`follow` also reports, under the same names,
the norm of its difference from the reference's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import dense_ref as R


def _shape(model: dict) -> tuple:
    return (model["vocab_size"], model["norm_eps"], model["rope_theta"])


@functools.partial(jax.jit, static_argnums=2)
def _fwd(x, p, shape):
    return R._layer(x, p, shape[1], shape[2])


@functools.partial(jax.jit, static_argnums=3)
def _bwd(x, p, dy, shape):
    _, vjp = jax.vjp(lambda x, p: R._layer(x, p, shape[1], shape[2]), x, p)
    return vjp(dy)


@jax.jit
def _embed(tok_embed, tokens):
    return tok_embed[tokens]


@jax.jit
def _embed_bwd(tok_embed, tokens, dx):
    _, vjp = jax.vjp(lambda e: e[tokens], tok_embed)
    return vjp(dx)[0]


def _head_nll(shared, x, labels, shape):
    vocab, eps, _ = shape
    x = R._rms(x, shared["final_norm"], eps)
    head = (shared["lm_head"] if "lm_head" in shared
            else shared["tok_embed"].T)[:, :vocab]
    logits = jnp.einsum("bsd,dv->bsv", x, head, precision=R.HIGHEST)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


_head = jax.jit(jax.value_and_grad(_head_nll, argnums=(0, 1)),
                static_argnums=3)
_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t),
                 donate_argnums=0)
_sq = jax.jit(lambda t: {k: jnp.sum(jnp.square(x)) for k, x in t.items()})
_diff_sq = jax.jit(lambda a, b: {k: jnp.sum(jnp.square(a[k] - b[k]))
                                 for k in a})


class Staged:
    """The weights from a seed, placed: ``shared`` (embedding, final
    norm, head) on ``devices[0]``, ``layers[l]`` (the layer's leaves,
    unstacked) on ``home[l]``; the work runs on ``devices[0]``."""

    def __init__(self, model: dict, seed: int, devices):
        self.model, self.dev = model, devices[0]
        n = model["n_layers"]
        per = -(-n // len(devices))
        self.home = [devices[l // per] for l in range(n)]
        self.shared, self.layers = self.initial(seed)

    def initial(self, seed: int):
        """``(shared, layers)``: :func:`dense_ref.init`'s weights, placed."""
        with jax.default_device(self.dev):
            full = jax.jit(functools.partial(R.init, self.model))(R.key(seed))
        stacked = full.pop("layers")
        layers = [jax.device_put({k: v[l] for k, v in stacked.items()}, d)
                  for l, d in enumerate(self.home)]
        return full, layers

    def loss_and_grad(self, batch: dict, rows_per_block: int):
        """``(mean loss, shared gradient, layer gradients, squared leaf
        norms of the gradient by part)`` over the batch, each layer run
        on ``rows_per_block`` rows at a time; each gradient on its
        weights' device."""
        dev, shape = self.dev, _shape(self.model)
        rows = range(0, batch["tokens"].shape[0], rows_per_block)
        tok = [jax.device_put(batch["tokens"][r:r + rows_per_block], dev)
               for r in rows]
        lab = [jax.device_put(batch["labels"][r:r + rows_per_block], dev)
               for r in rows]
        xs = [_embed(self.shared["tok_embed"], t) for t in tok]
        inputs = []
        for p, home in zip(self.layers, self.home):
            p = jax.device_put(p, dev)
            inputs.append([jax.device_put(x, home) for x in xs])
            xs = jax.block_until_ready([_fwd(x, p, shape) for x in xs])
        total, g_shared, dxs = 0.0, None, []
        for x, y in zip(xs, lab):
            s, (gs, dx) = _head(self.shared, x, y, shape)
            total += float(s)
            dxs.append(dx)
            g_shared = gs if g_shared is None else _add(g_shared, gs)
        n = batch["tokens"].size
        scale = jnp.float32(1.0 / n)
        g_layers = [None] * len(self.layers)
        sq = [None] * len(self.layers)
        for l in reversed(range(len(self.layers))):
            p, g = jax.device_put(self.layers[l], dev), None
            for r, x in enumerate(inputs[l]):
                dxs[r], gp = _bwd(jax.device_put(x, dev), p, dxs[r], shape)
                g = gp if g is None else _add(g, gp)
            inputs[l] = None
            g = _scale(g, scale)
            sq[l] = _sq(g)
            g_layers[l] = jax.block_until_ready(
                jax.device_put(g, self.home[l]))
        for t, dx in zip(tok, dxs):
            g_shared["tok_embed"] = g_shared["tok_embed"] + _embed_bwd(
                self.shared["tok_embed"], t, dx)
        g_shared = _scale(g_shared, scale)
        return total / n, g_shared, g_layers, [_sq(g_shared)] + sq


def _zeros(tree: dict, device) -> dict:
    return {k: jnp.zeros(x.shape, x.dtype, device=device)
            for k, x in tree.items()}


def _by_name(parts) -> dict:
    """Norms by leaf name from per-part dicts of squared norms."""
    sq: dict = {}
    for part in parts:
        for k, v in part.items():
            sq[k] = sq.get(k, 0.0) + float(v)
    return {k: float(np.sqrt(v)) for k, v in sq.items()}


def _dist_sq(grad: dict, g_shared: dict, g_layers: list, scale: float):
    """Squared norms, by part and leaf, of ``grad`` (host arrays in
    :func:`dense_ref.init`'s layout) less the reference's gradient times
    ``scale``; the difference is taken on the host."""
    def sq(a, b):
        d = a - np.asarray(b) * np.float32(scale)
        return float(np.vdot(d, d))
    return ([{k: sq(grad[k], g) for k, g in g_shared.items()}]
            + [{k: sq(grad["layers"][k][l], g) for k, g in part.items()}
               for l, part in enumerate(g_layers)])


def follow(model: dict, opt: dict, seed: int, batches, rows_per_block: int,
           devices, grad=None):
    """The reference's first ``len(batches)`` steps from the seed's
    weights, its state over ``devices``: ``(losses, first clipped
    gradient's leaf norms, leaf norms of the parameters' change after the
    last step)``, as :func:`dense_ref.follow` returns them, and the leaf
    norms of ``grad`` (a first clipped gradient on the host, in
    :func:`dense_ref.init`'s layout) less the reference's first clipped
    gradient, or None without ``grad``."""
    with jax.default_matmul_precision("highest"):
        st = Staged(model, seed, devices)
        dev = st.dev
        parts = [st.shared] + st.layers
        homes = [dev] + st.home
        m = [_zeros(p, d) for p, d in zip(parts, homes)]
        v = [_zeros(p, d) for p, d in zip(parts, homes)]
        losses, g1, dist = [], None, None
        for i, b in enumerate(batches, start=1):
            loss, g_shared, g_layers, sq = st.loss_and_grad(
                b, rows_per_block)
            losses.append(loss)
            norm = np.sqrt(sum(float(x) for s in sq for x in s.values()))
            scale = min(1.0, opt["grad_clip"] / (norm + 1e-9))
            if i == 1:
                g1 = {k: x * scale for k, x in _by_name(sq).items()}
                if grad is not None:
                    dist = _by_name(_dist_sq(grad, g_shared, g_layers,
                                             scale))
            grads = [g_shared] + g_layers
            del g_shared, g_layers
            for j, home in enumerate(homes):
                for k in sorted(parts[j]):
                    p, mk, vk = R._adamw_leaf(
                        *jax.device_put((parts[j][k], grads[j].pop(k),
                                         m[j][k], v[j][k]), dev),
                        jnp.float32(i), jnp.float32(scale), opt)
                    parts[j][k], m[j][k], v[j][k] = jax.device_put(
                        (p, mk, vk), home)
                jax.block_until_ready((parts[j], m[j], v[j]))
        del m, v, grads
        shared0, layers0 = st.initial(seed)
        dp = _by_name(
            [_diff_sq(parts[0], shared0)]
            + [_diff_sq(*jax.device_put((a, b), dev))
               for a, b in zip(parts[1:], layers0)])
    return losses, g1, dp, dist
