"""Configurator-overhead benchmark: the batched enumerate->prune pipeline
vs the seed's per-candidate scalar path, plus the end-to-end ``configure()``
phase breakdown.

    PYTHONPATH=src python -m benchmarks.bench_configure \
        [--nodes 16] [--quick] [--max-cp 4]

Phase A times memory pruning of the whole enumeration (MID_RANGE @ 16
nodes): the seed path paid one un-jitted one-row JAX forward per candidate
(dispatch-dominated), the new path one jitted ``predict_batch`` call on the
(N, F) feature matrix.  It also times profile construction the seed way
(every enumerated conf, before the memory check) vs the new way (survivors
only, memoized per ``(pp, tp, cp, bs_micro)``).

Phase B runs the full ``configure()`` search and prints the overhead
breakdown, exhaustive vs ``sa_topk``.

``--max-cp N`` (4D mode) opens the context-parallel axis: the enumeration
grows by the cp divisors of the sequence length, and the same batched
pipeline absorbs the larger candidate set — the point of ISSUE 3.  The
benchmark prints the 3D vs 4D candidate counts alongside the timings.

``--mixed-tier`` switches the cluster to the seeded mixed A100/V100 fleet
and appends Phase C: compute-aware vs compute-blind SA dedication of the
same configuration on the 16-node mixed fleet, both played back in the
discrete-event simulator at each rank's true speed — the heterogeneous-
compute headline (aware must be strictly faster).

Acceptance target (ISSUE 2): >= 5x on the enumerate+prune phase.

``--huge`` replaces all of the above with the 10k-GPU scaling curve
(ISSUE 6): full 4D plans of a seeded mixed A100/V100 fleet at 1k / 2k /
5k / 10k GPUs (``--quick``: 1k + 2k only, the CI smoke size), each size
planned by both SA backends of the unified core.  Per size it records the
plan wall-clock (numpy; jax cold; jax warm — second run with the
persistent XLA compilation cache populated), verifies the two backends
produced bit-identical plans, and measures full-re-score throughput
(``DedicationEngine.score`` loop vs the vmapped
``JaxDedicationEngine.score_batch``, steady state).  Gates, both fatal
(exit code 1):

* at every size >= 1024 GPUs the jitted batch scorer must not be slower
  than the NumPy engine at full re-scores (the vmapped core must earn its
  dispatch; the *plan*-level wall-clock is recorded un-gated — the
  incremental delta-scoring NumPy executor is expected to stay the better
  single-core-CPU choice, while the jitted path is the batched-rescore /
  accelerator story);
* when the 10240-GPU size runs, its (numpy-backend) plan must finish
  under ``--limit-s`` seconds (default 10 — the ROADMAP "plan a 10k-GPU
  cluster in seconds" target).

``--json PATH`` writes the machine-readable curve (the CI artifact).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import (MID_RANGE, Budget, ProfileCache, Workload,
                        build_profile, configure, dedicate_candidates,
                        enumerate_confs, fit_memory_estimator,
                        true_bandwidth_matrix)
from repro.core.cluster import (A100_TIER, V100_TIER, mixed_fleet_spec,
                                profile_bandwidth)
from repro.core.memory import _features, analytical_estimate
from repro.core.mlp import mlp_forward
from repro.core.simulator import Conf, default_mapping, measure
from repro.configs.gpt_paper import GPT_3_1B
from repro.models.config import ModelConfig

SEQ = 2048
BS_GLOBAL = 256


def scalar_predict_seed(est, cfg, conf) -> float:
    """The seed-era ``MemoryEstimator.predict``: per-call feature build and
    an un-jitted one-row MLP forward (one JAX dispatch per candidate)."""
    import jax.numpy as jnp
    x = (_features(cfg, conf, with_cp=est.with_cp) - est.x_mean) / est.x_std
    y = float(mlp_forward(est.params,
                          jnp.asarray(x[None], jnp.float32))[0, 0])
    pred = float(np.exp(y * est.y_std + est.y_mean))
    if est.residual:
        w = Workload(cfg, est.workload_seq, conf.bs_global)
        pred *= analytical_estimate(w, conf)
    return pred


# Row names used both for printing and for the speedup computation below.
# Keeping them as module constants (instead of free-floating strings looked
# up in a dict at report time) means a renamed row fails loudly at
# definition time, not as a KeyError after the benchmark already ran.
ROW_PRUNE_SEED = "prune scalar-predict (seed)"
ROW_PRUNE_COLD = "prune batched, cold (compile)"
ROW_PRUNE_BATCHED = "prune batched (new)"
ROW_PROFILES_SEED = "profiles seed (all, pre-prune)"
ROW_PROFILES_NEW = "profiles new (survivors, memoized)"


def bench_prune(w, spec, est, *, max_micro: int = 16, repeats: int = 3,
                max_cp: int = 1):
    """Enumerate+prune wall-clock, seed scalar path vs batched path.

    Yields ``(name, seconds, n_in, n_out)`` rows; the batched row is
    steady-state (first call pays the one-off XLA compile, reported as its
    own row).  The limit matches what ``run_search`` budgets — the
    tightest device tier (``mem_floor``; == ``gpu_mem`` when homogeneous),
    so the --mixed-tier survivor counts mirror the real pipeline's."""
    limit = spec.mem_floor * est.soft_margin

    def enumerate_filtered():
        return [c for c in enumerate_confs(spec.n_gpus, w.bs_global,
                                           n_layers=w.cfg.n_layers,
                                           max_cp=max_cp, seq=w.seq)
                if c.bs_micro <= max_micro]

    # seed path: one JAX dispatch per enumerated candidate
    best_scalar = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        confs = enumerate_filtered()
        kept = [c for c in confs
                if scalar_predict_seed(est, w.cfg, c) <= limit]
        dt = time.perf_counter() - t0
        best_scalar = dt if best_scalar is None else min(best_scalar, dt)
    yield (ROW_PRUNE_SEED, best_scalar, len(confs), len(kept))

    # batched path: cold call first (XLA compile), then steady state
    t0 = time.perf_counter()
    confs = enumerate_filtered()
    preds = est.predict_batch(w.cfg, confs)
    cold = time.perf_counter() - t0
    yield (ROW_PRUNE_COLD, cold, len(confs),
           int((preds <= limit).sum()))
    best_batch = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        confs = enumerate_filtered()
        preds = est.predict_batch(w.cfg, confs)
        kept_b = [c for c, k in zip(confs, preds <= limit) if k]
        dt = time.perf_counter() - t0
        best_batch = dt if best_batch is None else min(best_batch, dt)
    yield (ROW_PRUNE_BATCHED, best_batch, len(confs), len(kept_b))

    # profile construction: seed built one per enumerated conf *before* the
    # memory check; the new pipeline builds survivors only, memoized
    t0 = time.perf_counter()
    for c in confs:
        build_profile(w, spec, c)
    yield (ROW_PROFILES_SEED, time.perf_counter() - t0,
           len(confs), len(confs))
    t0 = time.perf_counter()
    cache = ProfileCache(w, spec)
    for c in kept_b:
        cache.get(c)
    yield (ROW_PROFILES_NEW, time.perf_counter() - t0,
           len(kept_b), len(cache._full))


def bench_search(w, spec, est, bw, *, sa_iters: int, max_micro: int,
                 sa_topk: int, max_cp: int = 1):
    """Full ``configure()`` wall-clock and phase breakdown, exhaustive SA vs
    the ``sa_topk`` concentration knob.  Yields ``(name, res)`` pairs."""
    kw = dict(estimator=est, sa_seconds=60.0, sa_iters=sa_iters,
              max_micro=max_micro, max_cp=max_cp, seed=0)
    yield ("configure() exhaustive SA", configure(w, spec, bw, **kw))
    yield (f"configure() sa_topk={sa_topk}",
           configure(w, spec, bw, sa_topk=sa_topk, **kw))


def bench_hetero_dedication(*, quick: bool):
    """Phase C: compute-aware vs compute-blind dedication on the seeded
    mixed A100/V100 16-node (single-GPU nodes) scenario, both simulated at
    true per-rank speed.  Prints the simulated latencies and a PASS /
    REGRESSION verdict (aware must be strictly faster than blind)."""
    gpt12 = ModelConfig(name="g12", family="dense", n_layers=12,
                        d_model=1024, n_heads=16, n_kv_heads=16, d_ff=4096,
                        vocab_size=32000)
    spec = mixed_fleet_spec("mixed-a100-v100-16x1", 16,
                            (A100_TIER, V100_TIER), (0.5, 0.5),
                            gpus_per_node=1, seed=47)
    w = Workload(gpt12, 2048, 32)
    conf = Conf(8, 1, 2, 2, 32)         # 4 heavy + 4 light (1-layer) stages
    bw, _ = profile_bandwidth(spec)
    bw_true = true_bandwidth_matrix(spec)
    prof = build_profile(w, spec, conf)
    iters = 10_000 if quick else 40_000
    budget = Budget(sa_seconds=60.0, sa_iters=iters, n_chains=4)
    t0 = time.perf_counter()
    aware = dedicate_candidates([conf], [prof], [0], bw, spec, budget, 0)[0]
    blind = dedicate_candidates([conf], [prof], [0], bw, spec, budget, 0,
                                compute_aware=False)[0]
    wall = time.perf_counter() - t0
    sim_aware = measure(conf, aware.mapping, w, spec, bw_true, seed=1)
    sim_blind = measure(conf, blind.mapping, w, spec, bw_true, seed=1)
    sim_default = measure(conf, default_mapping(conf), w, spec, bw_true,
                          seed=1)
    print()
    print(f"# phase C: hetero dedication on {spec.name} "
          f"({conf}, {iters} SA iters x2, {wall:.1f}s)")
    print("mapping,sim_latency_s")
    print(f"compute-aware SA,{sim_aware:.6f}")
    print(f"compute-blind SA,{sim_blind:.6f}")
    print(f"default (node-major),{sim_default:.6f}")
    gain = (1 - sim_aware / sim_blind) * 100
    verdict = "PASS" if sim_aware < sim_blind else "REGRESSION"
    print(f"compute-aware vs blind: {gain:+.1f}% simulated ({verdict})")
    return sim_aware < sim_blind


def bench_partition(*, quick: bool):
    """Phase D: DP layer partition vs the honest uniform split on the two
    non-uniform-cost configs (hybrid-attention zamba2, MoE kimi-k2), both
    played back in the discrete-event simulator at pp=8.  "Honest" means
    the uniform side also runs through the per-stage cost path (an explicit
    ceil-first :class:`Partition`), so the comparison isolates the split,
    not the cost model.  Prints per-model simulated latencies and a PASS /
    REGRESSION verdict (DP must be no slower than uniform on both)."""
    from repro.core import make_partition, uniform_partition
    from repro.configs.kimi_k2_1t_a32b import CONFIG as KIMI
    from repro.configs.zamba2_7b import CONFIG as ZAMBA

    spec = MID_RANGE.with_nodes(16)
    bw_true = true_bandwidth_matrix(spec)
    bs_global = 64 if quick else 256
    ok = True
    print()
    print(f"# phase D: DP vs uniform layer partition on {spec.name} "
          f"(pp=8, seq={SEQ}, bs_global={bs_global})")
    print("model,partition,stage_layers,sim_latency_s")
    for cfg in (ZAMBA, KIMI):
        w = Workload(cfg, SEQ, bs_global)
        conf = Conf(8, 4, 4, 2, bs_global)
        m = default_mapping(conf)
        part_u = uniform_partition(cfg.n_layers, conf.pp)
        part_dp = make_partition(cfg, conf.pp, SEQ, "dp")
        sim_u = measure(conf, m, w, spec, bw_true, seed=1,
                        partition=part_u)
        sim_dp = measure(conf, m, w, spec, bw_true, seed=1,
                         partition=part_dp)
        for label, part, sim in (("uniform", part_u, sim_u),
                                 ("dp", part_dp, sim_dp)):
            sizes = "/".join(str(s) for s in part.sizes)
            print(f"{cfg.name},{label},{sizes},{sim:.6f}")
        gain = (1 - sim_dp / sim_u) * 100
        verdict = "PASS" if sim_dp <= sim_u else "REGRESSION"
        print(f"{cfg.name}: dp vs uniform {gain:+.1f}% simulated "
              f"({verdict})")
        ok = ok and sim_dp <= sim_u
    return ok


# --------------------------------------------------------------------------
# --huge: the 10k-GPU scaling curve (ISSUE 6)
# --------------------------------------------------------------------------

#: 40 transformer layers so pipeline degrees with a factor of 5 are open
#: (10240 = 2^11 * 5 forces pp in {5, 10, 20, 40} once tp and dp take the
#: powers of two) — a GPT-13B-like shape.
M40 = ModelConfig(name="m40-13b", family="dense", n_layers=40, d_model=5120,
                  n_heads=40, n_kv_heads=40, d_ff=20480, vocab_size=32000)

HUGE_SIZES = (1024, 2048, 5120, 10240)
HUGE_QUICK_SIZES = (1024, 2048)
HUGE_BS_GLOBAL = 2048
RESCORE_BATCH = 16


def _huge_spec(n_gpus: int):
    return mixed_fleet_spec(f"huge-a100-v100-{n_gpus // 8}x8", n_gpus // 8,
                            (A100_TIER, V100_TIER), (0.5, 0.5),
                            gpus_per_node=8, seed=1234)


def _huge_plan(w, spec, bw, backend: str, *, sa_iters: int, n_chains: int):
    """One full 4D plan through the declarative API; returns
    ``(plan, wall_s)``.  Iteration-bound budget (the wall-clock guard can
    never bite) so numpy and jax runs are byte-comparable."""
    from repro.core import (Budget, Planner, PlanRequest, PipetteStrategy,
                            SearchSpace)
    req = PlanRequest(
        workload=w, spec=spec,
        space=SearchSpace(max_tp=8, max_cp=2, fixed_micro=1),
        budget=Budget(sa_seconds=3600.0, sa_iters=sa_iters,
                      n_chains=n_chains, sa_topk=2, backend=backend),
        seed=7)
    t0 = time.perf_counter()
    plan = Planner(PipetteStrategy()).plan(req, bw)
    return plan, time.perf_counter() - t0


def _bench_rescore(w, spec, bw, conf):
    """Steady-state full-re-score throughput of both engines on ``conf``:
    a Python loop of ``DedicationEngine.score`` vs one vmapped
    ``JaxDedicationEngine.score_batch`` dispatch over the same random
    permutations.  Returns ``(numpy_sps, jax_sps, jax_compile_s)`` in
    scores/second; asserts the two engines agree bitwise."""
    from repro.core import DedicationEngine, PairCache, build_profile
    from repro.core.jax_engine import JaxDedicationEngine
    prof = build_profile(w, spec, conf)
    pairs = PairCache.build(bw, spec.gpus_per_node)
    eng = DedicationEngine(conf, bw, prof, spec, pairs=pairs)
    jeng = JaxDedicationEngine([conf], [prof], bw, spec, pairs=pairs)
    rng = np.random.default_rng(0)
    perms = np.stack([rng.permutation(conf.n_gpus)
                      for _ in range(RESCORE_BATCH)])
    t_np = None
    for _ in range(3):
        t0 = time.perf_counter()
        np_vals = [eng.score(p) for p in perms]
        dt = time.perf_counter() - t0
        t_np = dt if t_np is None else min(t_np, dt)
    t0 = time.perf_counter()
    jax_vals = jeng.score_batch(perms)          # cold: pays the compile
    compile_s = time.perf_counter() - t0
    t_jx = None
    for _ in range(3):
        t0 = time.perf_counter()
        jax_vals = jeng.score_batch(perms)
        dt = time.perf_counter() - t0
        t_jx = dt if t_jx is None else min(t_jx, dt)
    assert all(float(a).hex() == float(b).hex()
               for a, b in zip(np_vals, jax_vals)), \
        "jax batch re-score diverged from the NumPy engine"
    return RESCORE_BATCH / t_np, RESCORE_BATCH / t_jx, compile_s


def bench_huge(args) -> None:
    """The ISSUE 6 scaling curve + gates; writes the ``--json`` artifact."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    sizes = [int(s) for s in args.sizes.split(",")] if args.sizes else \
        list(HUGE_QUICK_SIZES if args.quick else HUGE_SIZES)
    w = Workload(M40, SEQ, HUGE_BS_GLOBAL)
    failures: list[str] = []
    curve = []
    print(f"# --huge: {M40.name} seq={SEQ} bs_global={HUGE_BS_GLOBAL}, "
          f"mixed A100/V100 fleet, sa_iters={args.sa_iters} "
          f"n_chains={args.chains} sa_topk=2")
    print("n_gpus,bw_profile_s,numpy_plan_s,jax_cold_plan_s,"
          "jax_warm_plan_s,numpy_sa_s,jax_warm_sa_s,numpy_rescore_per_s,"
          "jax_rescore_per_s,latency_s,conf")
    for n in sizes:
        spec = _huge_spec(n)
        t0 = time.perf_counter()
        bw, _ = profile_bandwidth(spec)
        bw_s = time.perf_counter() - t0
        np_plan, np_s = _huge_plan(w, spec, bw, "numpy",
                                   sa_iters=args.sa_iters,
                                   n_chains=args.chains)
        jx_plan, jx_cold_s = _huge_plan(w, spec, bw, "jax",
                                        sa_iters=args.sa_iters,
                                        n_chains=args.chains)
        jx_plan2, jx_warm_s = _huge_plan(w, spec, bw, "jax",
                                         sa_iters=args.sa_iters,
                                         n_chains=args.chains)
        if (np_plan.conf != jx_plan.conf
                or float(np_plan.latency).hex()
                != float(jx_plan.latency).hex()
                or float(jx_plan2.latency).hex()
                != float(jx_plan.latency).hex()):
            failures.append(f"n={n}: backends disagree "
                            f"(numpy {np_plan.conf} {np_plan.latency!r} vs "
                            f"jax {jx_plan.conf} {jx_plan.latency!r})")
        np_sps, jx_sps, compile_s = _bench_rescore(w, spec, bw,
                                                   np_plan.conf)
        c = np_plan.conf
        cstr = f"pp{c.pp}.tp{c.tp}.cp{c.cp}.dp{c.dp}"
        print(f"{n},{bw_s:.2f},{np_s:.2f},{jx_cold_s:.2f},{jx_warm_s:.2f},"
              f"{np_plan.overhead.sa_s:.2f},{jx_plan2.overhead.sa_s:.2f},"
              f"{np_sps:.1f},{jx_sps:.1f},{np_plan.latency:.3f},{cstr}")
        curve.append({
            "n_gpus": n, "n_nodes": spec.n_nodes,
            "bw_profile_s": round(bw_s, 3),
            "numpy": {"plan_s": round(np_s, 3),
                      "sa_s": round(np_plan.overhead.sa_s, 3),
                      "rescore_per_s": round(np_sps, 1)},
            "jax": {"cold_plan_s": round(jx_cold_s, 3),
                    "warm_plan_s": round(jx_warm_s, 3),
                    "warm_sa_s": round(jx_plan2.overhead.sa_s, 3),
                    "rescore_per_s": round(jx_sps, 1),
                    "rescore_compile_s": round(compile_s, 3)},
            "latency_s": float(np_plan.latency), "conf": cstr,
            "n_enumerated": np_plan.overhead.n_enumerated,
        })
        # gate 1: the jitted batch scorer must not be slower than the
        # NumPy engine at full re-scores from 1k GPUs up
        if n >= 1024 and jx_sps < np_sps:
            failures.append(
                f"n={n}: jitted re-score slower than NumPy "
                f"({jx_sps:.1f} vs {np_sps:.1f} scores/s)")
        # gate 2: the 10k plan must land inside the ROADMAP budget
        if n >= 10240 and np_s > args.limit_s:
            failures.append(f"n={n}: plan took {np_s:.2f}s "
                            f"(limit {args.limit_s:.0f}s)")

    artifact = {
        "bench": "huge-scaling-curve", "model": M40.name, "seq": SEQ,
        "bs_global": HUGE_BS_GLOBAL, "sa_iters": args.sa_iters,
        "n_chains": args.chains, "sa_topk": 2, "seed": 7,
        "limit_s": args.limit_s, "sizes": sizes, "curve": curve,
        "gate_failures": failures,
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"# artifact -> {args.json}")
    if failures:
        raise SystemExit("--huge gate failures:\n  "
                         + "\n  ".join(failures))
    print(f"# gates PASS (jitted re-score >= NumPy at every size; "
          f"10k plan limit {args.limit_s:.0f}s)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=16,
                    help="cluster size in 8-GPU nodes (default 16)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: small estimator, tiny SA budget "
                         "(with --huge: the 1k+2k curve only)")
    ap.add_argument("--max-cp", type=int, default=1,
                    help="open the 4D context-parallel axis up to this "
                         "degree (default 1 = the 3D space)")
    ap.add_argument("--mixed-tier", action="store_true",
                    help="run on the seeded mixed A100/V100 fleet and "
                         "report compute-aware vs compute-blind dedication")
    ap.add_argument("--partition", action="store_true",
                    help="run only phase D: DP vs uniform layer partition "
                         "on the hybrid/MoE configs, simulated at pp=8")
    ap.add_argument("--huge", action="store_true",
                    help="run the 10k-GPU scaling curve instead of phases "
                         "A-C (see module docstring)")
    ap.add_argument("--sizes", default=None,
                    help="with --huge: comma-separated GPU counts "
                         "overriding the default curve")
    ap.add_argument("--sa-iters", type=int, default=200,
                    help="with --huge: SA refinement iterations per "
                         "candidate (default 200 — islands make the coarse "
                         "solution strong, refinement is a polish)")
    ap.add_argument("--chains", type=int, default=4,
                    help="with --huge: SA chains per candidate (default 4)")
    ap.add_argument("--limit-s", type=float, default=10.0,
                    help="with --huge: wall-clock budget for the 10k-GPU "
                         "plan (default 10s)")
    ap.add_argument("--json", default=None,
                    help="with --huge: write the scaling-curve artifact "
                         "to this path")
    args = ap.parse_args()

    if args.huge:
        bench_huge(args)
        return

    if args.partition:
        if not bench_partition(quick=args.quick):
            raise SystemExit(
                "partition regression: the DP split did not match or beat "
                "the uniform split in the simulator")
        return

    if args.mixed_tier:
        spec = mixed_fleet_spec("mixed-a100-v100", args.nodes,
                                (A100_TIER, V100_TIER), (0.5, 0.5),
                                gpus_per_node=8, intra_bw=300e9,
                                inter_bw=12.5e9, seed=47)
    else:
        spec = MID_RANGE.with_nodes(args.nodes)
    w = Workload(GPT_3_1B, SEQ, BS_GLOBAL)
    steps = 1000 if args.quick else 4000
    t0 = time.perf_counter()
    est = fit_memory_estimator([w], spec, fit_nodes=2, steps=steps,
                               residual=True, max_cp=args.max_cp)
    print(f"# estimator fit ({steps} steps, max_cp={args.max_cp}): "
          f"{time.perf_counter() - t0:.1f}s")
    if args.max_cp > 1:
        n3 = len(enumerate_confs(spec.n_gpus, w.bs_global,
                                 n_layers=w.cfg.n_layers))
        n4 = len(enumerate_confs(spec.n_gpus, w.bs_global,
                                 n_layers=w.cfg.n_layers,
                                 max_cp=args.max_cp, seq=w.seq))
        print(f"# 4D mode: search space {n3} (3D) -> {n4} confs "
              f"({n4 / max(n3, 1):.1f}x)")

    print("benchmark,wall_s,n_in,n_out")
    rows = {}
    for name, sec, n_in, n_out in bench_prune(w, spec, est,
                                              max_cp=args.max_cp):
        rows[name] = sec
        print(f"{name},{sec:.4f},{n_in},{n_out}")
    speedup = rows[ROW_PRUNE_SEED] / rows[ROW_PRUNE_BATCHED]
    prof_speedup = (rows[ROW_PROFILES_SEED]
                    / max(rows[ROW_PROFILES_NEW], 1e-9))
    print(f"enumerate+prune speedup: {speedup:.1f}x")
    print(f"profile-construction speedup: {prof_speedup:.1f}x")

    print()
    print("benchmark,total_s,sa_s,mem_estimator_s,profile_s,prescore_s,"
          "n_enumerated,n_candidates")
    bw = true_bandwidth_matrix(spec)
    sa_iters = 30 if args.quick else 150
    max_micro = 2 if args.quick else 4
    for name, res in bench_search(w, spec, est, bw, sa_iters=sa_iters,
                                  max_micro=max_micro, sa_topk=8,
                                  max_cp=args.max_cp):
        # typed Overhead attributes: a mistyped field is an AttributeError
        # here, not a KeyError swallowed into a half-printed CSV row
        o = res.overhead
        print(f"{name},{o.total_s:.2f},{o.sa_s:.2f},"
              f"{o.mem_estimator_s:.4f},{o.profile_s:.4f},"
              f"{o.prescore_s:.4f},{o.n_enumerated},"
              f"{o.n_candidates}")

    print()
    verdict = "PASS" if speedup >= 5.0 else "BELOW TARGET"
    print(f"enumerate+prune speedup {speedup:.1f}x (target >= 5x): {verdict}")

    if args.mixed_tier:
        ok = bench_hetero_dedication(quick=args.quick)
        if not ok:
            raise SystemExit(
                "mixed-tier regression: compute-aware dedication did not "
                "beat compute-blind in the simulator")


if __name__ == "__main__":
    main()
