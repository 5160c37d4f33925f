"""The benchmark's own operation and byte counts.

Kept apart from the program so that no change to the program can move
the yardstick: the parameter count of a dense decoder block (the
configurator's convention, a SwiGLU MLP of three matrices), the model
FLOPs of one trained token, and the operations and bytes of the
group-reduce kernels, read off each kernel call's shapes.
"""
from __future__ import annotations

import re

import numpy as np


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


def layer_params(m: dict) -> int:
    """Parameters of one dense decoder block: two RMSNorm weights,
    Q/K/V/O projections (with Q/K/V biases where ``qkv_bias``) and a
    three-matrix SwiGLU MLP."""
    d, h, hd = m["d_model"], m["n_heads"], head_dim(m)
    kv = m.get("n_kv_heads", h)
    bias = (h + 2 * kv) * hd if m.get("qkv_bias") else 0
    return int(2 * d + d * h * hd + 2 * d * kv * hd + h * hd * d + bias
               + 3 * d * m["d_ff"])


def param_count(m: dict) -> int:
    """Embedding, LM head (unless tied to the embedding), final norm and
    ``n_layers`` blocks."""
    d, v = m["d_model"], m["vocab_size"]
    heads = 1 if m.get("tie_embeddings") else 2
    return int(heads * v * d + d + m["n_layers"] * layer_params(m))


def attention_flops_per_token(m: dict, seq: int) -> float:
    """Score and value FLOPs of one token's forward pass over a causal
    sequence of ``seq``: ``2 * 2 * heads * head_dim * seq / 2`` a layer."""
    return float(m["n_layers"] * 2 * m["n_heads"] * head_dim(m) * seq)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward (3x the
    forward), recomputation not counted: ``6 * N_matmul`` for the weight
    matmuls (every block and the LM head, tied or not; the embedding is a
    lookup, the norms and biases are not matmuls) plus the attention
    scores and values."""
    d, v = m["d_model"], m["vocab_size"]
    h, hd = m["n_heads"], head_dim(m)
    kv = m.get("n_kv_heads", h)
    block = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * m["d_ff"]
    n_matmul = m["n_layers"] * block + d * v
    return 6.0 * n_matmul + 3.0 * attention_flops_per_token(m, seq)


# ---------------------------------------------------------------------------
# group-reduce kernels: operations and bytes from an HLO instruction's text
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
                "f64": 8, "s64": 8, "pred": 1, "s8": 1, "u8": 1}
# a shape, and its layout with the memory space (``S(1)``: on-chip VMEM)
_SHAPE = re.compile(r"\b(f32|s32|u32|bf16|f16|f64|s64|pred|s8|u8)"
                    r"\[([0-9,]*)\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
# ``%name = <output shapes> <opcode>(<operands>), <attributes>``
_INSTR = re.compile(r"^\s*%?\S+\s*=\s*(.*?)\s[a-z][\w-]*\((.*)$")


def _shape_elems(dims: str) -> int:
    return int(np.prod([int(x) for x in dims.split(",") if x])) if dims else 1


def _shapes(text: str):
    out = []
    for t, dims, layout in _SHAPE.findall(text):
        m = _SPACE.search(layout)
        out.append((t, _shape_elems(dims), int(m.group(1)) if m else 0))
    return out


def hlo_shapes(text: str):
    """``(output, operands)`` of an HLO instruction's text, each a list of
    ``(dtype, elements, memory space)``: the shapes left of the call's
    parenthesis are the output, those inside it its operands.  Memory
    space 0 is HBM."""
    m = _INSTR.match(text)
    if not m:
        return [], []
    return _shapes(m.group(1)), _shapes(m.group(2).split("), ", 1)[0])


def group_reduce_cost(text: str):
    """``(operations, HBM bytes)`` of one ``group_min_scale`` or
    ``group_max`` kernel call, from its HLO text.  Bytes: every operand in
    HBM read once and an output in HBM written once; operands that the
    compiler placed in on-chip memory (``S(1)``) move no HBM bytes.
    Operations: one compare per element reduced, and for the scale three
    more per output lane (the finiteness test, the divide and the select)."""
    out, ops = hlo_shapes(text)
    nbytes = sum(_DTYPE_BYTES[t] * n for t, n, space in out + ops
                 if space == 0)
    reduced = max((n for _, n, _ in ops), default=0)
    lanes = sum(n for _, n, _ in out)
    extra = 3 * lanes if "group_min_scale" in text.split("=", 1)[0] else 0
    return float(reduced + extra), float(nbytes)
