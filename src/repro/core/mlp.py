"""Minimal JAX MLP + Adam used by the memory estimator (paper §VI: five
layers, 200 hidden units, trained on profiled configurations)."""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def init_mlp(key, sizes: List[int]):
    params = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        key, k1 = jax.random.split(key)
        w = jax.random.normal(k1, (a, b), jnp.float32) * np.sqrt(2.0 / a)
        params.append({"w": w, "b": jnp.zeros((b,), jnp.float32)})
    return params


def mlp_forward(params, x):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i + 1 < len(params):
            x = jax.nn.gelu(x)
    return x


@jax.jit
def mlp_forward_rows(params, x):
    """Forward of each row of ``x`` on its own: one ``lax.map`` step per
    row, so row ``i`` of the output depends on ``x[i]`` alone and is
    bit-identical whatever else is in the batch — a one-row batch is the
    same computation.  A batched matmul gives no such guarantee: XLA tiles
    it by batch size, and rounding then varies with the row count.

    Shared by every estimator instance: jax.jit caches one trace per
    (param tree structure, batch shape); callers that pad batches to
    power-of-two buckets therefore hit a handful of traces total."""
    return jax.lax.map(lambda row: mlp_forward(params, row), x)


def pad_batch_rows(x: np.ndarray, minimum: int = 8) -> np.ndarray:
    """Zero-pad ``x`` along axis 0 to the next power-of-two row count.

    Bounds the number of distinct batch shapes :func:`mlp_forward_rows`
    ever sees (log2 of the largest batch), so candidate-set sizes that vary
    from call to call do not each pay an XLA retrace.

    Args:
        x: ``(n, f)`` feature matrix.
        minimum: smallest bucket size.

    Returns:
        ``(m, f)`` array with ``m = max(minimum, 2**ceil(log2(n)))``.
    """
    n = x.shape[0]
    m = max(minimum, 1 << (n - 1).bit_length())
    if m == n:
        return x
    return np.concatenate(
        [x, np.zeros((m - n,) + x.shape[1:], x.dtype)], axis=0)


@functools.partial(jax.jit, static_argnames=("steps", "lr"))
def train_mlp(params, x, y, *, steps: int = 20_000, lr: float = 1e-3):
    """Full-batch Adam regression on (x, y) with cosine LR decay."""
    def loss_fn(p):
        pred = mlp_forward(p, x)[:, 0]
        return jnp.mean((pred - y) ** 2)

    def adam_step(state, _):
        p, m, v, t = state
        g = jax.grad(loss_fn)(p)
        t = t + 1
        cur_lr = lr * (0.02 + 0.98 * 0.5 *
                       (1 + jnp.cos(jnp.pi * t / steps)))
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        mh = jax.tree.map(lambda a: a / (1 - 0.9 ** t), m)
        vh = jax.tree.map(lambda a: a / (1 - 0.999 ** t), v)
        p = jax.tree.map(
            lambda a, mm, vv: a - cur_lr * mm / (jnp.sqrt(vv) + 1e-8),
            p, mh, vh)
        return (p, m, v, t), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (params, zeros, jax.tree.map(jnp.zeros_like, params),
             jnp.zeros((), jnp.float32))
    state, _ = jax.lax.scan(adam_step, state, None, length=steps)
    return state[0]
