"""The benchmark's own synthetic token batches: a fresh batch of uniform
token ids for every step, from ``(seed, step)``; labels are the next
token of the same row, so every label is valid."""
from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, rows: int, seq: int, vocab: int) -> dict:
    """``{"tokens", "labels"}``, each ``(rows, seq)`` int32."""
    x = np.random.default_rng([seed, 0x70C, step]).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return {"tokens": x[:, :-1], "labels": x[:, 1:]}
