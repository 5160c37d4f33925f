"""Group-reduce kernels for the JAX dedication scorer.

The vmapped SA core spends its inner step reducing many small gathered
bandwidth sub-matrices — per communicator group, the min link bandwidth
turned into a slowdown scale (TP / CP groups), and per pipeline stage the
max member compute slowdown.  Both reductions are Pallas TPU kernels here,
laid out the way Mosaic tiles a vreg: the group index runs along the
128-wide lane axis (padded to a lane multiple), the small per-group axis
(``m·m`` link entries, or the stage's members) runs down the sublanes and
is reduced, and each grid step writes one lane-dense ``(1, block)`` row.
The kernels compute in float32 (a TPU has no native float64); the
reference bandwidth of the slowdown scale is an SMEM scalar.

Each kernel has a pure-jnp reference (``*_ref``) in the caller's dtype.
Rounding to float32 is monotonic, so a kernel's min or max is exactly the
float32 rounding of the reference's: equal for float32 inputs, within one
float32 rounding (relative ``2**-24``) for float64 ones.  The scale adds
one float32 rounding of ``ref_bw`` and one of the divide, so it sits
within ``3 * 2**-24`` relative of the reference.  ``tests/test_jax_engine``
pins both bounds.  The scorer (``repro.core.jax_engine``) runs the kernels
on TPU and the references elsewhere; ``interpret=True`` runs a kernel in
the Pallas interpreter, which only tests ask for.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: Lane width of one grid step; at most 64 x 1024 float32 (256 KiB) of
#: VMEM per input buffer for the largest (tp = 8) groups.
BLOCK_LANES = 1024
#: Row tile of the group-max reduction, which accumulates over row blocks
#: when a stage has more members than this.
BLOCK_ROWS = 1024

# Block indices must be int32 for Mosaic, also when the caller traces
# under x64 (where a bare ``0`` would lower as int64).
_ZERO = np.int32(0)


def _lane_layout(n: int):
    """Padded lane count and lane block for ``n`` groups."""
    padded = -(-n // LANES) * LANES
    block = min(padded, BLOCK_LANES)
    return -(-padded // block) * block, block


# ---------------------------------------------------------------------------
# per-group min-bandwidth -> slowdown scale
# ---------------------------------------------------------------------------

def group_min_scale_ref(sub: jax.Array, ref_bw) -> jax.Array:
    """Per-group slowdown scales from gathered bandwidth sub-matrices.

    Args:
        sub: ``(n_groups, m, m)`` pairwise link bandwidths of each
            communicator group (self links pre-masked to ``inf``).
        ref_bw: scalar bandwidth the profiled time was measured at.

    Returns:
        ``(n_groups,)`` scales: ``ref_bw / min(sub)`` where the group min
        is finite and positive, else 1.0 (the degenerate-link guard of
        ``latency._tp_scale``).
    """
    gbw = sub.min(axis=(1, 2))
    ok = jnp.isfinite(gbw) & (gbw > 0)
    return jnp.where(ok, ref_bw / gbw, 1.0)


def _min_scale_kernel(refbw_ref, sub_ref, o_ref):
    gbw = jnp.min(sub_ref[...], axis=0, keepdims=True)
    ok = (gbw > 0) & (gbw < jnp.inf)
    o_ref[...] = jnp.where(ok, refbw_ref[0] / gbw, 1.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def group_min_scale(sub: jax.Array, ref_bw, *,
                    interpret: bool = False) -> jax.Array:
    """Pallas TPU version of :func:`group_min_scale_ref`, in float32."""
    n, m, _ = sub.shape
    padded, block = _lane_layout(n)
    # (m*m, n): groups on lanes; padded groups reduce to an all-inf min,
    # are masked to scale 1.0, then sliced away
    x = sub.reshape(n, m * m).T.astype(jnp.float32)
    x = jnp.pad(x, ((0, 0), (0, padded - n)), constant_values=jnp.inf)
    refbw = jnp.full((1,), ref_bw, jnp.float32)
    out = pl.pallas_call(
        _min_scale_kernel,
        grid=(padded // block,),
        in_specs=[pl.BlockSpec((1,), lambda i: (_ZERO,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((m * m, block), lambda i: (_ZERO, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (_ZERO, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        interpret=interpret,
        name="group_min_scale",
    )(refbw, x)
    return out[0, :n]


# ---------------------------------------------------------------------------
# per-stage max member slowdown
# ---------------------------------------------------------------------------

def group_max_ref(vals: jax.Array) -> jax.Array:
    """Row-wise max: ``(n_rows, m) -> (n_rows,)`` (per-stage compute
    slowdown reduce of the tiered-cluster path)."""
    return vals.max(axis=1)


def _max_kernel(v_ref, o_ref):
    part = jnp.max(v_ref[...], axis=0, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = part

    @pl.when(pl.program_id(1) > 0)
    def _():
        o_ref[...] = jnp.maximum(o_ref[...], part)


@functools.partial(jax.jit, static_argnames=("interpret",))
def group_max(vals: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Pallas TPU version of :func:`group_max_ref`, in float32."""
    n, m = vals.shape
    padded, block = _lane_layout(n)
    rows = min(m, BLOCK_ROWS)
    m_pad = -(-m // rows) * rows
    # (m, n): rows on lanes, members reduced down the sublanes; padding is
    # -inf, the identity of max
    x = vals.T.astype(jnp.float32)
    x = jnp.pad(x, ((0, m_pad - m), (0, padded - n)),
                constant_values=-jnp.inf)
    out = pl.pallas_call(
        _max_kernel,
        grid=(padded // block, m_pad // rows),
        in_specs=[pl.BlockSpec((rows, block), lambda i, k: (k, i))],
        out_specs=pl.BlockSpec((1, block), lambda i, k: (_ZERO, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="group_max",
    )(x)
    return out[0, :n]
