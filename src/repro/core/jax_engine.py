"""JAX-native dedication scorer and vmapped multi-chain annealer.

This is the ``backend="jax"`` execution engine of the unified SA core
(``repro.core.annealing``): the Eq. 3-6 mapping score is re-expressed as a
pure function of a flat permutation device array, the move-propose /
score / accept loop becomes a ``lax.scan``, and the scan is ``vmap``-ed
across annealing chains *and* across the same-shape candidate
configurations — one XLA dispatch advances every chain of every candidate.

The score runs in float64 under a scoped ``jax.enable_x64`` and mirrors
:class:`repro.core.dedication.DedicationEngine` reduction by reduction
(min/max reductions are order-insensitive; the pipeline-chain hop
accumulation replays the reference's left-to-right fold; the tiered
per-stage sum replays NumPy's pairwise summation order via
:func:`np_pairwise_sum`).

The group-reduce inner step (per-group min-bandwidth scales, per-stage
max compute slowdown) is chosen by the platform: on TPU the float32
Pallas kernels of ``repro.kernels.group_reduce``, elsewhere their
float64 jnp references.  ``kernels="interpret"`` runs the kernels in the
Pallas interpreter and ``kernels="pallas"`` compiles them whatever the
default backend is; only tests ask for either.

What "equal" means therefore depends on the platform:

* On CPU the score is bit-identical to the NumPy engine, and
  ``tests/test_backend_determinism.py`` pins byte-identical ``Plan`` JSON
  across backends.  One compiler setting guards this: XLA's CPU backend
  contracts ``a * b + c`` into a fused multiply-add on FMA hosts, 1 ulp
  off NumPy's separate multiply and add — enough to flip an SA accept
  decision.  Only the process-wide flag ``--xla_cpu_max_isa=AVX`` (the
  last x86 vector ISA without FMA) stops it; the installed XLA ignores it
  as a per-compile option.  The test suite sets it in ``XLA_FLAGS``.
* On TPU the kernels round the group minima and maxima to float32 (and
  the scorer's float64 is emulated), so a score is within
  :data:`TPU_REL_TOL` of the float64 reference ``pipette_latency_ref``,
  not bit-equal to it.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from .cluster import ClusterSpec, compute_slowdowns
from .dedication import PairCache
from .simulator import Conf, Profile

import jax
import jax.numpy as jnp

from ..kernels.group_reduce import (group_max, group_max_ref,
                                    group_min_scale, group_min_scale_ref)


def np_pairwise_sum(x, n: int):
    """Sum ``x[:n]`` in exactly NumPy's pairwise-summation order.

    ``np.sum`` on a contiguous float64 vector is *not* a left fold: it runs
    an 8-accumulator blocked pairwise scheme, so ``jnp.sum`` (a flat XLA
    reduce) differs from it in the last bits for almost any ``n >= 3``.
    The tiered-cluster combine (``latency._hetero_combine``) sums the
    per-stage compute vector with ``np.sum``, so the JAX scorer replays the
    same association order element by element.  Works on NumPy arrays and
    traced JAX values alike (the loop structure is host-side Python over a
    static length); pinned bit-exact against ``np.sum`` in
    ``tests/test_jax_engine.py``.
    """
    def pw(lo, m):
        if m < 8:
            res = 0.0
            for i in range(m):
                res = res + x[lo + i]
            return res
        if m <= 128:
            r = [x[lo + k] for k in range(8)]
            i = 8
            while i + 8 <= m:
                for k in range(8):
                    r[k] = r[k] + x[lo + i + k]
                i += 8
            res = ((r[0] + r[1]) + (r[2] + r[3])) + \
                ((r[4] + r[5]) + (r[6] + r[7]))
            while i < m:
                res = res + x[lo + i]
                i += 1
            return res
        m2 = (m // 2) - ((m // 2) % 8)
        return pw(lo, m2) + pw(lo + m2, m - m2)

    return pw(0, n)


#: Relative tolerance of a TPU score against the float64 reference
#: ``pipette_latency_ref``.  Each kernel output is within ``3 * 2**-24``
#: (1.8e-7) relative of its reference (``repro.kernels.group_reduce``).
#: The score is a sum of nonnegative terms, each proportional to at most
#: one kernel output (a TP or CP scale, or a stage's slowdown); the one
#: difference, ``c_sum - c_max``, errs by at most ``2 * 3 * 2**-24`` of
#: ``c_sum``, which the bubble term ``pp * c_max * n_mb / pp`` bounds.  So
#: the kernels move a score by a few times 1.8e-7, and the bound leaves
#: room for the emulated float64 arithmetic around them.
TPU_REL_TOL = 1e-6


def kernels_mode(kernels: str = "auto") -> str:
    """Resolve the group-reduce implementation: ``"auto"`` is the compiled
    Pallas kernel on TPU and the jnp reference elsewhere; ``"pallas"`` and
    ``"interpret"`` (tests only) force a kernel."""
    if kernels in ("pallas", "interpret"):
        return kernels
    if kernels != "auto":
        raise ValueError(f"kernels must be auto|pallas|interpret, "
                         f"got {kernels!r}")
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _apply_move(perm, pos, kind, pa, pb):
    """One SA move as an index remap (all three variants are computed and
    the ``kind`` selects — cheap O(n) selects, no dynamic shapes).

    Semantics (shared with ``annealing._move_numpy``): with
    ``i = min(pa, pb)``, ``j = max(pa, pb)`` — migration (kind 0) removes
    the element at ``i`` and reinserts it at ``j``; swap (kind 1)
    exchanges positions ``i`` and ``j``; reverse (kind 2) reverses the
    span ``[i, j]``.
    """
    i = jnp.minimum(pa, pb)
    j = jnp.maximum(pa, pb)
    mig = jnp.where((pos >= i) & (pos < j), pos + 1,
                    jnp.where(pos == j, i, pos))
    swp = jnp.where(pos == i, j, jnp.where(pos == j, i, pos))
    rev = jnp.where((pos >= i) & (pos <= j), i + j - pos, pos)
    src = jnp.where(kind == 0, mig, jnp.where(kind == 1, swp, rev))
    return perm[src]


class JaxDedicationEngine:
    """Batched JAX scorer + vmapped multi-chain SA for one (pp, tp, cp, dp)
    shape.

    One engine serves every same-shape candidate (microbatch variants):
    the shape-only tensors (pair-bandwidth matrices, ring coefficients,
    device slowdowns) are shared device arrays, while the per-candidate
    profile scalars form the vmapped axis.  ``score()`` is the full
    evaluator (equal to ``DedicationEngine.score`` as the module docstring
    says, pinned by the equivalence suite); :meth:`anneal` runs the vmapped
    chains-x-candidates ``lax.scan``.

    Args:
        confs: same-shape candidate configurations.
        profs: ``profs[i]`` is the profile of ``confs[i]``; the shape-only
            fields (``tp_ref_bw``/``cp_ref_bw``/``msg_dp``/``stage_work``)
            must agree across candidates (asserted — true of
            ``build_profile`` output for one workload).
        bw: ``(G, G)`` profiled bandwidth matrix.
        spec: cluster description.
        kernels: group-reduce implementation knob (see
            :func:`kernels_mode`).
        compute_aware: ``False`` prices every GPU at reference speed even
            on tiered specs (the compute-blind ablation), mirroring
            ``DedicationEngine``.
        pairs: optional prebuilt :class:`~repro.core.dedication.PairCache`
            for this ``(bw, spec)`` — skips the host-side O(G^2)
            construction when the driver already built one.
        device_pairs: optional ``.device_pairs`` of a sibling engine built
            for the *same* ``(bw, spec, compute_aware)`` — shares the big
            (G, G) device buffers across shape groups instead of paying
            the host->device copy (~2.5 GB at 10k GPUs) per group.
    """

    def __init__(self, confs: Sequence[Conf], profs: Sequence[Profile],
                 bw: np.ndarray, spec: ClusterSpec, *,
                 kernels: str = "auto", compute_aware: bool = True,
                 pairs: Optional[PairCache] = None,
                 device_pairs: Optional[dict] = None):
        conf = confs[0]
        shape = (conf.pp, conf.tp, conf.cp, conf.dp, conf.vpp)
        for c in confs[1:]:
            if (c.pp, c.tp, c.cp, c.dp, c.vpp) != shape:
                raise ValueError("JaxDedicationEngine needs same-shape confs")
        p0 = profs[0]
        for p in profs[1:]:
            assert (p.tp_ref_bw, p.cp_ref_bw, p.msg_dp, p.stage_work,
                    p.partition, p.chunk_work) == \
                (p0.tp_ref_bw, p0.cp_ref_bw, p0.msg_dp, p0.stage_work,
                 p0.partition, p0.chunk_work), \
                "profiles vary within shape; shared tensors invalid"
        self.confs = list(confs)
        self.pp, self.tp, self.cp, self.dp, self.vpp = shape
        self.n = conf.n_gpus
        self.nc = self.tp * self.cp * self.dp
        self.tpc = self.tp * self.cp
        self._kmode = kernels_mode(kernels)
        self._tp_ref = float(p0.tp_ref_bw)
        self._cp_ref = float(p0.cp_ref_bw)

        # host-side constants: the (G, G) pair matrices come from the same
        # PairCache construction the NumPy engine shares (bit-identical by
        # design), the small per-shape tensors are built here
        if pairs is None:
            pairs = PairCache.build(bw, spec.gpus_per_node)
        jlt = (np.arange(self.dp)[None, :] < np.arange(self.dp)[:, None])
        intra_coef = np.array(
            [4 * (c - 1) / c * p0.msg_dp if c else 0.0
             for c in range(self.dp + 1)])
        inter_coef = np.array(
            [2 * (c - 1) / c * p0.msg_dp if c else 0.0
             for c in range(self.dp + 1)])
        slow = compute_slowdowns(spec) if compute_aware else None
        self.tiered = slow is not None
        # Non-uniform partitions / interleaved schedules need the per-stage
        # combination even without device tiers (latency._combine_eq34's
        # trigger, mirrored here so both backends stay bit-identical).
        self.nonuniform = p0.partition is not None or conf.vpp > 1

        # per-candidate profile scalars (the vmapped axis); all arithmetic
        # on host NumPy f64 so the values equal the NumPy engine's
        w = (np.asarray(p0.stage_work) if p0.stage_work is not None
             else np.ones(self.pp))
        c_arr = np.array([p.c_fwd + p.c_bwd for p in profs])
        sc = {
            "c": c_arr,
            "tsum_tp": np.array([p.t_tp_fwd + p.t_tp_bwd for p in profs]),
            "tsum_cp": np.array([p.t_cp_fwd + p.t_cp_bwd for p in profs]),
            "hopf": np.array([2.0 * p.msg_pp for p in profs]),
            "r": np.array([c.n_mb / c.pp for c in confs]),
            "cw": (c_arr[:, None] * w[None, :]
                   if self.tiered or self.nonuniform else None),
        }

        # device residency in f64 — arrays must be created inside the
        # scoped x64 context or jnp silently downcasts them to f32.  The
        # (G, G) tensors travel as *arguments* of the jitted functions,
        # never as closure constants: XLA embeds (and constant-folds)
        # captured constants into the executable, which at 10k GPUs means
        # gigabytes of f64 baked into every compile.
        with jax.enable_x64(True):
            if device_pairs is None:
                device_pairs = {
                    "bw": jnp.asarray(pairs.bw),
                    "bw_noself": jnp.asarray(pairs.bw_noself),
                    "sym_intra": jnp.asarray(pairs.sym_intra),
                    "slow": None if slow is None else jnp.asarray(slow),
                }
            self.device_pairs = device_pairs
            self._env = {
                **device_pairs,
                "jlt": jnp.asarray(jlt),
                "intra_coef": jnp.asarray(intra_coef),
                "inter_coef": jnp.asarray(inter_coef),
            }
            self._sc = {k: (None if v is None else jnp.asarray(v))
                        for k, v in sc.items()}
        self._jit_score = None
        self._batch_cache = {}
        self._anneal_cache = {}

    # -- the pure scoring function (one perm, one candidate's scalars) ----

    def _group_scales(self, sub, ref_bw):
        if self._kmode == "ref":
            return group_min_scale_ref(sub, ref_bw)
        return group_min_scale(sub, ref_bw,
                               interpret=(self._kmode == "interpret"))

    def _group_max(self, vals):
        if self._kmode == "ref":
            return group_max_ref(vals)
        return group_max(vals, interpret=(self._kmode == "interpret"))

    def _score_one(self, perm, sc, env):
        """Full Eq. 3-6 evaluation of one permutation; every reduction
        mirrors ``DedicationEngine`` (see the module docstring for what
        that makes equal on each platform)."""
        pp, tp, cp, dp = self.pp, self.tp, self.cp, self.dp
        nc, tpc = self.nc, self.tpc

        if tp > 1:
            g = perm.reshape(-1, tp)
            sub = env["bw_noself"][g[:, :, None], g[:, None, :]]
            tp_scale = jnp.maximum(1.0, self._group_scales(
                sub, self._tp_ref).max())
        else:
            tp_scale = 1.0

        if cp > 1:
            g = perm.reshape(pp * dp, cp, tp).transpose(0, 2, 1) \
                .reshape(-1, cp)
            sub = env["bw_noself"][g[:, :, None], g[:, None, :]]
            cp_scale = jnp.maximum(1.0, self._group_scales(
                sub, self._cp_ref).max())
        else:
            cp_scale = 1.0

        if pp > 1:
            src = perm[:(pp - 1) * nc].reshape(pp - 1, nc)
            dst = perm[nc:].reshape(pp - 1, nc)
            hop = sc["hopf"] / env["bw"][src, dst]
            t = hop[0]
            for x in range(1, pp - 1):       # reference left-to-right fold
                t = t + hop[x]
            t_pp = jnp.maximum(0.0, t.max())
        else:
            t_pp = 0.0

        # stage-0 DP hierarchical all-reduce (Eq. 6); the only DP groups on
        # the critical path — mirrors DedicationEngine._dp0_times
        ids = perm[:nc].reshape(dp, tpc).T                    # (tpc, dp)
        ii, jj = ids[:, :, None], ids[:, None, :]
        sym = env["sym_intra"][ii, jj]
        member_min = sym.min(axis=2)
        same = jnp.isfinite(sym)
        counts = same.sum(axis=2) + 1  # repro: noqa DET003 -- boolean mask count: integer reduction, exact in any association order
        intra = (env["intra_coef"][counts] / member_min).max(axis=1)
        is_rep = ~(same & env["jlt"]).any(axis=2)
        n_reps = is_rep.sum(axis=1)  # repro: noqa DET003 -- boolean mask count: integer reduction, exact in any association order
        pair = is_rep[:, :, None] & is_rep[:, None, :]
        rep_min = jnp.where(pair, env["bw_noself"][ii, jj],
                            jnp.inf).min(axis=(1, 2))
        inter = env["inter_coef"][n_reps] / rep_min
        t_dp = jnp.maximum(0.0, (intra + inter).max())

        t_tp = sc["tsum_tp"] * tp_scale
        t_cm = t_tp + sc["tsum_cp"] * cp_scale
        if self.tiered or self.nonuniform:
            if self.tiered:
                sv = self._group_max(env["slow"][perm.reshape(pp, nc)])
                c_x = sc["cw"] * sv
            else:
                # homogeneous fleet, non-uniform stage_work: the NumPy
                # engine's stage scales are all 1.0, and cw * 1.0 == cw
                # exactly, so using cw directly preserves bit parity
                c_x = sc["cw"]
            c_max = c_x.max()
            c_sum = np_pairwise_sum(c_x, pp)
            if self.vpp == 1:
                t_bubble = float(pp) * (c_max + t_cm) + t_pp
                return ((t_bubble * sc["r"] + (c_sum - c_max))
                        + float(pp - 1) * t_cm) + t_dp
            # interleaved-1F1B: mirrors _hetero_combine's vpp branch in
            # NumPy's left-to-right association order
            t_bubble = float(pp) * (c_max + t_cm) + float(self.vpp) * t_pp
            return ((t_bubble * sc["r"] + (c_sum - c_max) / float(self.vpp))
                    + float(pp - 1) * t_cm / float(self.vpp)) + t_dp
        t_bubble = float(pp) * (sc["c"] + t_cm) + t_pp
        t_straggler = float(pp - 1) * (sc["c"] + t_cm)
        return (t_bubble * sc["r"] + t_straggler) + t_dp

    # -- public scoring (tests / coarse assignment) -----------------------

    def score(self, perm: np.ndarray, cand: int = 0) -> float:
        """Full JAX evaluation of ``perm`` for candidate ``cand`` — the
        same value as ``DedicationEngine(confs[cand], ...).score(perm)``
        (bitwise on CPU, within :data:`TPU_REL_TOL` on TPU)."""
        with jax.enable_x64(True):
            sc = {k: (None if v is None else v[cand])
                  for k, v in self._sc.items()}
            p = jnp.asarray(np.asarray(perm), dtype=jnp.int32)
            if self._jit_score is None:
                self._jit_score = jax.jit(self._score_one).lower(
                    p, sc, self._env).compile()
            return float(self._jit_score(p, sc, self._env))

    def score_batch(self, perms: np.ndarray, cand: int = 0) -> np.ndarray:
        """Score a ``(R, n)`` batch of permutations in one vmapped dispatch.

        Element ``r`` equals ``score(perms[r], cand)`` bitwise — the batch
        axis only amortises dispatch and lets XLA pipeline the gathers.
        This is the unit of work the ``--huge`` benchmark's throughput gate
        measures against a loop of NumPy-engine full re-scores.
        """
        with jax.enable_x64(True):
            sc = {k: (None if v is None else v[cand])
                  for k, v in self._sc.items()}
            p = jnp.asarray(np.asarray(perms), dtype=jnp.int32)
            exe = self._batch_cache.get(p.shape)
            if exe is None:
                exe = jax.jit(jax.vmap(self._score_one,
                                       in_axes=(0, None, None))).lower(
                    p, sc, self._env).compile()
                self._batch_cache[p.shape] = exe
            return np.asarray(exe(p, sc, self._env))

    # -- the vmapped multi-chain annealer ---------------------------------

    def _build_anneal(self, alpha: float):
        pos = jnp.arange(self.n, dtype=jnp.int32)

        def run_chain(init_perm, pas, pbs, kinds, thresh, valid,
                      ppas, ppbs, pkinds, sc, env):
            cur0 = self._score_one(init_perm, sc, env)

            def probe(carry, xs):
                pk, pa, pb = xs
                val = self._score_one(
                    _apply_move(init_perm, pos, pk, pa, pb), sc, env)
                return jnp.maximum(carry, jnp.abs(val - cur0)), None

            mx, _ = jax.lax.scan(probe, 0.0, (pkinds, ppas, ppbs))
            temp0 = jnp.maximum(jnp.maximum(mx, cur0 * 1e-3), 1e-12)

            def step(carry, xs):
                perm, cur, temp, best, bperm, acc, accb = carry
                kind, pa, pb, thr, ok = xs
                cand = _apply_move(perm, pos, kind, pa, pb)
                val = self._score_one(cand, sc, env)
                delta = val - cur
                accept = ok & ((delta <= 0) | (delta < temp * thr))
                perm = jnp.where(accept, cand, perm)
                cur = jnp.where(accept, val, cur)
                acc = acc + accept.astype(acc.dtype)
                imp = accept & (val < best)
                best = jnp.where(imp, val, best)
                bperm = jnp.where(imp, cand, bperm)
                accb = jnp.where(imp, acc, accb)
                temp = jnp.where(ok, temp * alpha, temp)
                return (perm, cur, temp, best, bperm, acc, accb), None

            zero = jnp.zeros((), jnp.int32)
            carry0 = (init_perm, cur0, temp0, cur0, init_perm, zero, zero)
            (_, cur, _, best, bperm, acc, accb), _ = jax.lax.scan(
                step, carry0, (kinds, pas, pbs, thresh, valid))
            return best, bperm, cur, acc, accb

        over_chains = jax.vmap(
            run_chain,
            in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, None, None))
        over_cands = jax.vmap(
            over_chains,
            in_axes=(0, 0, 0, None, None, None, 0, 0, None, 0, None))
        return over_cands

    def anneal_args(self, init_perms: np.ndarray, pas: np.ndarray,
                    pbs: np.ndarray, kinds: np.ndarray, thresh: np.ndarray,
                    valid: np.ndarray, probe_pas: np.ndarray,
                    probe_pbs: np.ndarray, probe_kinds: np.ndarray) -> tuple:
        """Device arguments of the annealer (see :meth:`anneal` for the
        host arrays), float64 where the scorer needs it."""
        with jax.enable_x64(True):
            i32 = jnp.int32
            return (jnp.asarray(init_perms, dtype=i32),
                    jnp.asarray(pas, dtype=i32), jnp.asarray(pbs, dtype=i32),
                    jnp.asarray(kinds, dtype=i32),
                    jnp.asarray(thresh), jnp.asarray(valid),
                    jnp.asarray(probe_pas, dtype=i32),
                    jnp.asarray(probe_pbs, dtype=i32),
                    jnp.asarray(probe_kinds, dtype=i32), self._sc,
                    self._env)

    def compile_anneal(self, args: tuple, alpha: float = 0.999):
        """The compiled annealer for ``args`` (arrays from
        :meth:`anneal_args`, or ``jax.ShapeDtypeStruct`` s of them).

        Executables are shape-specialized and ``alpha`` is baked into the
        scan body, so both key the per-engine cache."""
        key = (tuple(np.shape(a) for a in args[:9]), alpha)
        exe = self._anneal_cache.get(key)
        if exe is None:
            with jax.enable_x64(True):
                exe = jax.jit(self._build_anneal(alpha)).lower(
                    *args).compile()
            self._anneal_cache[key] = exe
        return exe

    def anneal(self, init_perms: np.ndarray, pas: np.ndarray,
               pbs: np.ndarray, kinds: np.ndarray, thresh: np.ndarray,
               valid: np.ndarray, probe_pas: np.ndarray,
               probe_pbs: np.ndarray, probe_kinds: np.ndarray, *,
               alpha: float = 0.999):
        """Advance every chain of every candidate in one jitted dispatch.

        Args:
            init_perms: ``(C, n)`` start permutation per candidate.
            pas / pbs: ``(C, K, T)`` absolute move positions (island
                offsets already applied per candidate).
            kinds: ``(K, T)`` move kinds, shared across candidates.
            thresh: ``(K, T)`` precomputed ``-log(u)`` accept thresholds.
            valid: ``(K, T)`` per-chain iteration mask (False iterations
                are no-ops — chains may have unequal budgets).
            probe_pas / probe_pbs: ``(C, K, P)`` temperature-probe moves.
            probe_kinds: ``(K, P)``.
            alpha: geometric temperature decay.

        Returns:
            ``(bests, best_perms, finals, accepted, accepted_to_best)``
            NumPy arrays of shapes ``(C, K)``, ``(C, K, n)``, ``(C, K)``,
            ``(C, K)``, ``(C, K)`` — the last two are each chain's total
            accepted moves and the accepted-move count at which it first
            reached its best (0 = never improved on the init), matching
            :func:`~repro.core.annealing._run_chain_numpy` exactly.
        """
        args = self.anneal_args(init_perms, pas, pbs, kinds, thresh, valid,
                                probe_pas, probe_pbs, probe_kinds)
        best, bperm, fin, acc, accb = self.compile_anneal(args, alpha)(*args)
        return (np.asarray(best), np.asarray(bperm, dtype=np.int64),
                np.asarray(fin), np.asarray(acc, dtype=np.int64),
                np.asarray(accb, dtype=np.int64))
