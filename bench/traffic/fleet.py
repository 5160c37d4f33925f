"""The benchmark's own fleet generator: device tiers laid out over nodes in
a seeded shuffle, and a measured-like link-bandwidth matrix drawn per
link from ``--seed``.

The layout follows the configurator's mixed-fleet convention: node
counts per tier from the tier fractions (remainders to the leading
tiers), a seeded shuffle of the node order.  The links follow its
profile model: per node pair a lognormal factor clipped to [0.35, 1.15]
of the nominal inter-node bandwidth, a straggler share at half speed,
near-symmetric; intra-node links jitter in [0.92, 1.0] of nominal; every
entry is then read with 1% Gaussian measurement noise.  Only the
generated ``(tiers, node_tiers)`` and the matrix reach the program.
"""
from __future__ import annotations

import math

import numpy as np


def node_tiers(n_nodes: int, fractions, layout_seed: int) -> np.ndarray:
    """Tier index of every node: counts from ``fractions``, the leading
    tiers taking the remainder, in a shuffle seeded by ``layout_seed``."""
    total = math.fsum(fractions)
    counts = [int(f / total * n_nodes) for f in fractions]
    present = [i for i, f in enumerate(fractions) if f > 0]
    for k in range(n_nodes - sum(counts)):
        counts[present[k % len(present)]] += 1
    assignment = np.repeat(np.arange(len(fractions)), counts)
    np.random.default_rng(layout_seed * 999983 + 7).shuffle(assignment)
    return assignment


def bandwidth_matrix(fleet: dict, seed: int) -> np.ndarray:
    """``(G, G)`` measured link bandwidths in bytes/s, from ``seed``."""
    rng = np.random.default_rng([seed, 0xB4])
    nn, gpn = fleet["n_nodes"], fleet["gpus_per_node"]
    g = nn * gpn
    f = np.exp(rng.normal(0.0, fleet["heterogeneity"], (nn, nn)))
    f = np.clip(f, 0.35, 1.15)
    slow = rng.random((nn, nn)) < fleet["slow_frac"]
    f = np.where(slow, f * 0.5, f)
    f = np.minimum(f, f.T * rng.uniform(0.96, 1.04, (nn, nn)))
    np.fill_diagonal(f, 1.0)
    node = np.arange(g) // gpn
    same = node[:, None] == node[None, :]
    intra_jit = rng.uniform(0.92, 1.0, (g, g))
    bw = np.where(same, fleet["intra_bw"] * intra_jit,
                  fleet["inter_bw"] * f[node[:, None], node[None, :]])
    np.fill_diagonal(bw, fleet["intra_bw"] * 4)     # a device to itself
    return bw * rng.normal(1.0, fleet["profile_noise"], bw.shape)
