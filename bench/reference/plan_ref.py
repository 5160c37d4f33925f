"""Plain reference of the planner's answer: the iteration time that
Pipette's latency model (arXiv 2405.18093, Eq. 3-6) gives a plan's
configuration and worker-to-device mapping, written out in loops with
nothing taken from the program.

The model, as the configurator states it for plain 1F1B on the uniform
layer split of a tiered fleet:

* per microbatch, forward compute ``c_fwd`` = the heaviest stage's
  FLOPs (``2 * params`` of its layers, the attention scores and values,
  the embedding and head amortised over ``pp``) over the reference
  device's attained throughput (``flops * efficiency * 1.25 *
  bs_micro / (bs_micro + 1) * tp``); backward is twice forward;
* tensor-parallel all-reduces ``2 * layers`` a direction (ring of the
  ``bs_micro * seq * d_model`` bf16 activation), scaled by the slowest
  TP group's bottleneck link against the nominal one; context-parallel
  ring exchanges likewise;
* the slowest pipeline chain's hops, ``2 * msg_pp / bw`` each;
* stage 0's hierarchical data-parallel all-reduce (node-local ring,
  then one representative per node), its slowest TP/CP column;
* stages priced at their slowest member's tier:
  ``T = (pp (c_max + t_cm) + t_pp) n_mb / pp + (sum c_x - c_max)
  + (pp - 1) t_cm + t_dp``.

``dtype`` sets the arithmetic: float64, as the configurator scores, or
float32, the control that must fail the comparison.
"""
from __future__ import annotations

import numpy as np

from .. import flops as F


def _min_link(bw, ids) -> float:
    """Slowest link among ``ids`` (both directions); inf for one member."""
    ids = list(ids)
    if len(ids) < 2:
        return np.inf
    return min(bw[a, b] for a in ids for b in ids if a != b)


def _ring(msg, link, n, phases, f):
    return f(0) if n <= 1 else f(phases) * f(n - 1) / f(n) * msg / link


def latency(conf: dict, mapping, bw: np.ndarray, model: dict, job: dict,
            fleet: dict, slow: np.ndarray, dtype=np.float64) -> float:
    """Seconds per iteration of ``conf`` under ``mapping``.

    Args:
        conf: ``pp, tp, cp, dp, bs_micro, bs_global`` (``vpp`` must be 1).
        mapping: worker -> device ids, reshapeable to ``(pp, tp, cp, dp)``.
        bw: measured ``(G, G)`` link bandwidths.
        model / job / fleet: the configuration file's sections.
        slow: per-device compute slowdown against the fastest tier.
        dtype: the arithmetic's float type.
    """
    f = np.dtype(dtype).type
    pp, tp, cp, dp = conf["pp"], conf["tp"], conf["cp"], conf["dp"]
    mb, bs = conf["bs_micro"], conf["bs_global"]
    if conf.get("vpp", 1) != 1:
        raise ValueError("the reference covers plain 1F1B only")
    m4 = np.asarray(mapping).reshape(pp, tp, cp, dp)
    bw = np.asarray(bw).astype(dtype)
    slow = np.asarray(slow).astype(dtype)
    L, d, V = model["n_layers"], model["d_model"], model["vocab_size"]
    H, hd = model["n_heads"], F.head_dim(model)
    kv = model.get("n_kv_heads", H)
    seq, gpn = job["seq"], fleet["gpus_per_node"]
    n_mb = bs // dp // mb

    # profiled per-microbatch quantities
    ref_tier = max(fleet["tiers"], key=lambda t: t["flops"] * t["efficiency"])
    tp_ref = f(fleet["intra_bw"] if tp <= gpn else fleet["inter_bw"])
    p_total = F.param_count(model)
    stage_params = f(p_total - 2 * V * d) / f(pp) \
        + f(2 * V * d) / f(min(pp, 2))
    msg_dp = stage_params / f(tp) * f(job["grad_bytes"])
    layers_stage = -(-L // pp)
    tokens_mb = f(mb) * f(seq) / f(cp)
    body = max(p_total - 2 * V * d, int(0.5 * p_total))
    fwd = f(2) * (f(body) * f(layers_stage) / f(L)) * tokens_mb
    fwd = fwd + f(layers_stage) * f(2 * H * hd * seq) * tokens_mb
    fwd = fwd + f(4 * V * d) * tokens_mb / f(pp)
    thru = (f(ref_tier["flops"]) * f(ref_tier["efficiency"]) * f(1.25)
            * (f(mb) / f(mb + 1)) * f(tp))
    c = fwd / thru * f(3)                              # forward + backward
    msg = f(mb) * f(seq) * f(d) * f(2) / f(cp)         # bf16 activation
    t_tp = f(3) * f(2 * layers_stage) * _ring(msg, tp_ref, tp, 2, f)
    if cp > 1:
        cp_ref = f(fleet["intra_bw"] if tp * cp <= gpn else fleet["inter_bw"])
        msg_cp = f(4) * f(mb) * (f(seq) / f(cp)) * f(kv * hd)
        t_cp = f(3) * f(layers_stage * (cp - 1)) * msg_cp / cp_ref
    else:
        cp_ref, t_cp = tp_ref, f(0)

    # the mapping's communication and compute scales
    tp_scale = f(1)
    for x in range(pp):
        for k in range(cp):
            for z in range(dp):
                link = _min_link(bw, m4[x, :, k, z])
                if np.isfinite(link) and link > 0:
                    tp_scale = max(tp_scale, tp_ref / link)
    cp_scale = f(1)
    for x in range(pp):
        for y in range(tp):
            for z in range(dp):
                link = _min_link(bw, m4[x, y, :, z])
                if np.isfinite(link) and link > 0:
                    cp_scale = max(cp_scale, cp_ref / link)
    t_cm = t_tp * tp_scale + t_cp * cp_scale
    t_pp = f(0)
    for y in range(tp):
        for k in range(cp):
            for z in range(dp):
                t = f(0)
                for x in range(pp - 1):
                    t = t + f(2) * msg / bw[m4[x, y, k, z], m4[x + 1, y, k, z]]
                t_pp = max(t_pp, t)
    t_dp = f(0)
    for y in range(tp):
        for k in range(cp):
            nodes: dict = {}
            for g in m4[0, y, k, :]:
                nodes.setdefault(int(g) // gpn, []).append(int(g))
            intra = f(0)
            for members in nodes.values():
                if len(members) > 1:
                    intra = max(intra, _ring(msg_dp, _min_link(bw, members),
                                             len(members), 4, f))
            reps = [m[0] for m in nodes.values()]
            inter = (_ring(msg_dp, _min_link(bw, reps), len(reps), 2, f)
                     if len(reps) > 1 else f(0))
            t_dp = max(t_dp, intra + inter)

    full, base, rem = -(-L // pp), L // pp, L % pp
    cx = []
    for x in range(pp):
        w = f(base + 1 if x < rem else base) / f(full)
        cx.append(c * w * max(slow[int(g)] for g in m4[x].ravel()))
    c_max = max(cx)
    c_sum = f(0)
    for v in cx:
        c_sum = c_sum + v
    return float((f(pp) * (c_max + t_cm) + t_pp) * (f(n_mb) / f(pp))
                 + (c_sum - c_max) + f(pp - 1) * t_cm + t_dp)


def mapping_faults(conf: dict, mapping, n_devices: int, n_layers: int) -> int:
    """Count of broken guarantees of a plan's configuration and mapping:
    the degrees cover the fleet, the batch splits evenly, 1F1B has at
    least ``pp`` microbatches, every stage has a layer, and the mapping is
    a permutation of the fleet's devices."""
    pp, tp, cp, dp = conf["pp"], conf["tp"], conf["cp"], conf["dp"]
    mb, bs = conf["bs_micro"], conf["bs_global"]
    flat = np.asarray(mapping).ravel()
    faults = [pp * tp * cp * dp != n_devices,
              bs % dp != 0 or (bs // dp) % mb != 0,
              (bs // dp) // mb < pp,
              n_layers < pp,
              flat.size != n_devices
              or not np.array_equal(np.sort(flat), np.arange(n_devices))]
    return sum(faults)
