"""The paper's headline experiment at full scale: configure GPT-3.1B
training on the simulated 128-GPU mid-range cluster and compare Pipette
(PPT-L / PPT-LF) against Megatron-LM, Varuna and AMP (Fig. 6) — all five
configurators running behind the single Planner API, as one loop over
strategies instead of four bespoke call sites.

``--cluster mid-range-degraded`` runs the same pipeline on a partially-
degraded fleet (a quarter of the hosts thermally throttled to half speed,
seeded): the search prices each pipeline stage at its slowest member GPU,
and a closing demo compares compute-aware vs compute-blind worker
dedication of the winning configuration in the simulator.

    PYTHONPATH=src python examples/configure_cluster.py [--cluster high-end]
"""
import argparse
import time

from repro.core import (HIGH_END, MID_RANGE, MID_RANGE_DEGRADED,
                        AMPStrategy, Budget, ExhaustiveStrategy,
                        MegatronStrategy, Planner, PlanRequest,
                        PipetteStrategy, SearchSpace, VarunaStrategy,
                        Workload, build_profile, dedicate_candidates,
                        fit_memory_estimator, ground_truth_memory, measure,
                        profile_bandwidth, true_bandwidth_matrix)
from repro.configs.gpt_paper import GPT_3_1B, GPT_11_1B

CLUSTERS = {"mid-range": MID_RANGE, "high-end": HIGH_END,
            "mid-range-degraded": MID_RANGE_DEGRADED}


def first_runnable(ranked, w, spec):
    for i, c in enumerate(ranked):
        if ground_truth_memory(w, c.conf, spec) <= spec.mem_floor:
            return c, i + 1
    return None, len(ranked)


def degraded_host_demo(base_w, spec, bw_meas, bw_true, *, seed=0):
    """Where per-GPU compute awareness pays on a degraded fleet.

    A deep pipeline over a layer count ``pp`` does not divide leaves
    *light* stages (fewer layers) beside heavy ones — the one place a
    throttled host can serve without pacing the whole pipeline.  The demo
    dedicates a pp=16 configuration of a 24-layer variant two ways —
    node-major default (tier-blind) vs compute-aware placement (slow hosts
    onto the light stages, then SA polish) — and plays both back in the
    simulator at true per-rank speed.
    """
    import dataclasses

    import numpy as np

    from repro.core import compute_slowdowns
    from repro.core.simulator import Conf

    cfg24 = dataclasses.replace(base_w.cfg, name=base_w.cfg.name + "-24L",
                                n_layers=24)
    w = Workload(cfg24, base_w.seq, 32)
    conf = Conf(16, 8, 1, 2, 32)          # 8 heavy + 8 light (1-layer) stages
    prof = build_profile(w, spec, conf)
    slow = compute_slowdowns(spec)
    # compute-aware placement: fastest GPUs serve the heavy leading stages,
    # throttled hosts sink to the light trailing ones; SA polishes comm
    greedy = tuple(int(g) for g in np.argsort(slow, kind="stable"))
    budget = Budget(sa_seconds=10.0, sa_iters=10_000, n_chains=2,
                    warm_start=greedy)
    aware = dedicate_candidates([conf], [prof], [0], bw_meas, spec, budget,
                                seed)[0]
    sim_aware = measure(conf, aware.mapping, w, spec, bw_true, seed=1)
    from repro.core import default_mapping
    sim_blind = measure(conf, default_mapping(conf), w, spec, bw_true,
                        seed=1)
    deg = [i for i, t in enumerate(spec.node_tiers) if t == 1]
    print(f"\n[degraded] throttled nodes (half speed): {deg}")
    print(f"[degraded] dedication of {conf} ({cfg24.n_layers} layers -> "
          f"8 heavy + 8 light stages), simulated:")
    print(f"  tier-blind node-major {sim_blind * 1e3:9.1f} ms/iter")
    print(f"  compute-aware + SA    {sim_aware * 1e3:9.1f} ms/iter "
          f"({(1 - sim_aware / sim_blind) * 100:+.1f}%)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster", choices=sorted(CLUSTERS),
                    default="mid-range")
    ap.add_argument("--sa-seconds", type=float, default=1.0)
    ap.add_argument("--save-plan", default=None, metavar="PATH",
                    help="write the PPT-LF Plan JSON artifact here")
    args = ap.parse_args()

    spec = CLUSTERS[args.cluster]
    model = GPT_11_1B if args.cluster == "high-end" else GPT_3_1B
    w = Workload(model, 2048, 256)
    print(f"cluster: {spec.name} ({spec.n_gpus} GPUs), model {model.name}")

    bw_true = true_bandwidth_matrix(spec)
    bw_meas, cost = profile_bandwidth(spec)
    print(f"[profile] bandwidth matrix measured "
          f"(~{cost:.0f}s on the real cluster)")

    t0 = time.time()
    est = fit_memory_estimator(
        [Workload(model, 2048, bsg) for bsg in (64, 128, 256, 512)], spec,
        fit_nodes=4, steps=12_000, residual=True)
    print(f"[memest] MLP fitted on <=4-node profiles in {time.time()-t0:.0f}s")

    # one declarative request, five strategies behind one interface
    req = PlanRequest(
        workload=w, spec=spec, space=SearchSpace(),
        budget=Budget(sa_seconds=args.sa_seconds, sa_iters=20_000),
        seed=1)
    strategies = [
        # the Megatron heuristic's trial runs execute on the real cluster
        # (the ground-truth matrix), not the profiled snapshot
        ("Megatron-LM (tp=8 heuristic)", MegatronStrategy(bw_true=bw_true)),
        ("Varuna (pp-only)", VarunaStrategy()),
        ("AMP", AMPStrategy()),
        ("Pipette PPT-L", ExhaustiveStrategy(estimator=est,
                                             mem_limit=spec.mem_floor)),
        ("Pipette PPT-LF", PipetteStrategy(estimator=est,
                                           mem_limit=spec.mem_floor)),
    ]

    rows, ppt_plan, ppt_best, sa_time = [], None, None, 0.0
    for label, strategy in strategies:
        t0 = time.time()
        plan = Planner(strategy).plan(req, bw_meas)
        elapsed = time.time() - t0
        # memory-unaware baselines: a human walks the ranking until one
        # actually fits — count those trial runs against them
        best, trials = first_runnable(plan.result.ranked, w, spec)
        if trials > 1:
            label = f"{label} (runnable after {trials} trials)"
        t_iter = measure(best.conf, best.mapping, w, spec, bw_true)
        rows.append((label, best.conf, t_iter))
        if strategy.name == "pipette":
            ppt_plan, ppt_best, sa_time = plan, best, elapsed

    base = next(t for name, _, t in rows if name.startswith("AMP"))
    print(f"\n{'method':38s} {'config':28s} {'iter ms':>9s} {'vs AMP':>7s}")
    for name, conf, t in rows:
        print(f"{name:38s} {str(conf):28s} {t*1e3:9.1f} {base/t:7.2f}x")
    print(f"\n[pipette] total search time {sa_time:.0f}s "
          f"(SA dedication per candidate config)")
    # ppt_best is the candidate the table row measured (== plan.conf unless
    # the estimator under-predicted and first_runnable stepped down the
    # ranking) — print the dedication of what we reported, not blindly
    # ranked[0]
    print(f"[pipette] worker dedication for {ppt_best.conf} "
          "(GPU ids, stages x (tp*dp)):")
    print(ppt_best.mapping.reshape(ppt_best.conf.pp, -1))
    if args.save_plan:
        if ppt_best.conf != ppt_plan.conf:
            # index into the full ranking first_runnable searched, not the
            # top-k the artifact keeps (the fallback may sit below rank 10)
            rank = [c.conf for c in ppt_plan.result.ranked] \
                .index(ppt_best.conf)
            print(f"[pipette] note: artifact best {ppt_plan.conf} was not "
                  f"runnable; the measured row used fallback ranked[{rank}]")
        print(f"[pipette] plan artifact -> {ppt_plan.save(args.save_plan)}")

    if spec.has_tiers:
        degraded_host_demo(w, spec, bw_meas, bw_true)


if __name__ == "__main__":
    main()
