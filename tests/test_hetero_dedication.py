"""Compute-aware worker dedication on tiered clusters.

Covers the three contracts of the heterogeneous-compute engine:

1. **bit-equality** — the incremental :class:`DedicationEngine` equals
   :func:`pipette_latency` and the pure-Python ``pipette_latency_ref``
   oracle on tiered specs, through long propose/commit chains;
2. **the headline** (acceptance criterion) — on a seeded mixed A100/V100
   16-node cluster, compute-aware dedication yields *strictly lower
   simulated* iteration latency than compute-blind dedication of the same
   configuration;
3. **plumbing** — search-level integration: per-GPU memory floor, tier
   provenance on the Plan, compute-aware scores inside ``run_search``.
"""
import numpy as np
import pytest

from repro.core import (MIXED_A100_V100, Budget, Conf, DedicationEngine,
                        Planner, PlanRequest, PipetteStrategy, SearchSpace,
                        Workload, build_profile, configure,
                        dedicate_candidates, default_mapping, pipette_latency,
                        pipette_latency_ref, profile_bandwidth,
                        true_bandwidth_matrix)
from repro.core.cluster import (A100_TIER, V100_TIER, compute_slowdowns,
                                mixed_fleet_spec, tier_fingerprint)
from repro.core.annealing import _move_numpy
from repro.core.dedication import perm_to_mapping
from repro.core.simulator import measure
from repro.models.config import ModelConfig

GPT = ModelConfig(name="g12", family="dense", n_layers=12, d_model=1024,
                  n_heads=16, n_kv_heads=16, d_ff=4096, vocab_size=32000)

# The headline scenario: 16 single-GPU nodes, half A100 / half V100 in a
# seeded shuffle.  pp=8 over 12 layers leaves four light (1-layer) stages
# next to four heavy (2-layer) stages — exactly where slow GPUs hurt least.
MIXED_16 = mixed_fleet_spec("mixed-a100-v100-16x1", 16,
                            (A100_TIER, V100_TIER), (0.5, 0.5),
                            gpus_per_node=1, seed=47)
HEADLINE_CONF = Conf(8, 1, 2, 2, 32)
W = Workload(GPT, 2048, 32)


# ---------------------------------------------------------------------------
# engine == model == reference, bit for bit, on tiered specs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("conf", [
    Conf(4, 8, 4, 2, 256),               # 3D on the 128-GPU mixed preset
    Conf(4, 4, 4, 2, 256, cp=2),         # 4D
])
def test_engine_matches_model_and_ref_on_tiered_spec(conf):
    spec = MIXED_A100_V100
    w = Workload(GPT, 2048, 256)
    bw, _ = profile_bandwidth(spec)
    prof = build_profile(w, spec, conf)
    eng = DedicationEngine(conf, bw, prof, spec)
    rng = np.random.default_rng(11)
    perm = np.arange(conf.n_gpus)
    eng.score(perm)
    n = conf.n_gpus
    for trial in range(120):
        kind, pa, pb = (int(v) for v in rng.integers((3, n, n - 1)))
        cand, touched = _move_numpy(perm, kind, pa, pb + (pb >= pa))
        val, pending = eng.propose(cand, touched)
        m = perm_to_mapping(cand, conf)
        assert val == pipette_latency(conf, m, bw, prof, spec)
        if trial % 10 == 0:
            assert val == pipette_latency_ref(conf, m, bw, prof, spec)
        if trial % 3 == 0:
            eng.commit(pending)
            perm = cand
    assert eng.score(perm) == pipette_latency(
        conf, perm_to_mapping(perm, conf), bw, prof, spec)


def test_compute_blind_engine_ignores_tiers():
    """``compute_aware=False`` prices every GPU at reference speed: its
    scores equal the same spec with the tier table erased."""
    import dataclasses
    spec = MIXED_16
    flat = dataclasses.replace(spec, tiers=(), node_tiers=())
    conf = HEADLINE_CONF
    bw, _ = profile_bandwidth(spec)
    prof = build_profile(W, spec, conf)
    blind = DedicationEngine(conf, bw, prof, spec, compute_aware=False)
    ref = DedicationEngine(conf, bw, prof, flat)
    perm = np.random.default_rng(0).permutation(conf.n_gpus)
    assert blind.score(perm) == ref.score(perm)


def test_hetero_latency_penalises_slow_stages():
    """A mapping that herds V100s onto light stages scores strictly better
    than one spreading them over every stage — the signal SA climbs."""
    spec = MIXED_16
    conf = HEADLINE_CONF
    bw, _ = profile_bandwidth(spec)
    prof = build_profile(W, spec, conf)
    slow = compute_slowdowns(spec)
    fast_first = np.argsort(slow, kind="stable")     # A100s, then V100s
    herded = perm_to_mapping(fast_first, conf)       # V100s on late (light)
    spread = default_mapping(conf)
    assert pipette_latency(conf, herded, bw, prof, spec) < \
        pipette_latency(conf, spread, bw, prof, spec)


# ---------------------------------------------------------------------------
# the headline: aware beats blind in the *simulator*
# ---------------------------------------------------------------------------

def test_compute_aware_beats_blind_in_simulator():
    """Acceptance criterion: on the seeded mixed A100/V100 16-node cluster,
    compute-aware SA dedication of HEADLINE_CONF simulates strictly faster
    than compute-blind SA dedication of the same conf (same budget, same
    seed), and than the default node-major assignment."""
    spec = MIXED_16
    conf = HEADLINE_CONF
    bw, _ = profile_bandwidth(spec)
    bw_true = true_bandwidth_matrix(spec)
    prof = build_profile(W, spec, conf)
    budget = Budget(n_chains=4, sa_seconds=30.0, sa_iters=40_000)
    aware = dedicate_candidates([conf], [prof], [0], bw, spec, budget, 0)[0]
    blind = dedicate_candidates([conf], [prof], [0], bw, spec, budget, 0,
                                compute_aware=False)[0]
    sim_aware = measure(conf, aware.mapping, W, spec, bw_true, seed=1)
    sim_blind = measure(conf, blind.mapping, W, spec, bw_true, seed=1)
    sim_default = measure(conf, default_mapping(conf), W, spec, bw_true,
                          seed=1)
    assert sim_aware < sim_blind
    assert sim_aware < sim_default
    # the win is structural (slow GPUs herded onto light stages), not noise
    assert sim_aware < 0.9 * sim_blind


# ---------------------------------------------------------------------------
# search / plan integration
# ---------------------------------------------------------------------------

def test_search_prunes_against_tightest_tier():
    """Without an explicit mem_limit the search must budget for the
    *smallest* GPU (every GPU hosts a worker): the default limit on the
    mixed preset is the V100's 32 GB, not the A100 reference's 80 GB."""
    from repro.core.search import run_search

    class Probe:
        """Estimator stub predicting a constant peak for every conf."""
        soft_margin = 1.0
        with_cp = False

        def __init__(self, pred):
            self.pred = pred

        def predict_batch(self, cfg, confs):
            return np.full(len(confs), self.pred)

    assert MIXED_16.mem_floor == V100_TIER.mem
    req = PlanRequest(workload=W, spec=MIXED_16,
                      space=SearchSpace(max_micro=2),
                      budget=Budget(sa_seconds=60.0, sa_iters=5, sa_topk=1))
    bw, _ = profile_bandwidth(MIXED_16)
    # a 40 GB peak fits the A100 reference (80 GB) but not the V100 floor
    # (32 GB): everything must be pruned
    res = run_search(req, bw, estimator=Probe(40e9))
    assert res.best is None and not res.ranked
    # under the floor, the tiered pipeline runs end-to-end
    res = run_search(req, bw, estimator=Probe(10e9))
    assert res.best is not None


def test_estimator_fits_spec_uses_per_gpu_capacity():
    """fits_spec budgets for the tightest tier: a peak that fits the A100
    reference but not the V100 floor must be rejected on the mixed fleet
    and accepted on an all-A100 fleet of the same shape."""
    import dataclasses

    from repro.core import MemoryEstimator
    est = MemoryEstimator.__new__(MemoryEstimator)
    est.soft_margin = 1.0
    est.predict = lambda cfg, conf: 40e9          # between 32 GB and 80 GB
    conf = HEADLINE_CONF
    assert not est.fits_spec(GPT, conf, MIXED_16)
    all_a100 = dataclasses.replace(
        MIXED_16, node_tiers=(0,) * MIXED_16.n_nodes)
    assert est.fits_spec(GPT, conf, all_a100)


def test_plan_records_tier_provenance():
    spec = MIXED_16
    bw, _ = profile_bandwidth(spec)
    req = PlanRequest(workload=W, spec=spec,
                      space=SearchSpace(max_micro=2),
                      budget=Budget(sa_seconds=60.0, sa_iters=20,
                                    sa_topk=2), seed=5)
    plan = Planner(PipetteStrategy()).plan(req, bw)
    tiers = plan.provenance.tiers
    assert tiers is not None
    assert tiers["digest"] == tier_fingerprint(spec)
    assert [t["name"] for t in tiers["tiers"]] == ["a100", "v100"]
    assert tiers["node_tiers"] == [int(t) for t in spec.node_tiers]
    d = plan.to_json_dict()
    assert d["provenance"]["tiers"]["digest"] == tier_fingerprint(spec)
    # homogeneous plans keep the key, with null
    import dataclasses
    flat = dataclasses.replace(spec, tiers=(), node_tiers=())
    req_h = PlanRequest(workload=W, spec=flat,
                        space=SearchSpace(max_micro=2),
                        budget=Budget(sa_seconds=60.0, sa_iters=20,
                                      sa_topk=2), seed=5)
    plan_h = Planner(PipetteStrategy()).plan(req_h, bw)
    assert plan_h.provenance.tiers is None
    assert plan_h.to_json_dict()["provenance"]["tiers"] is None


def test_elastic_replan_keeps_tier_pattern():
    """Losing nodes on a mixed fleet re-plans against the surviving tier
    mix: the shrunk spec keeps the tier pattern and the resulting Plan's
    provenance records the new (different) tier digest."""
    from repro.runtime.elastic import replan
    ep = replan(W, MIXED_16, 12, sa_seconds=60.0, sa_iters=20, sa_topk=2,
                max_micro=2)
    assert ep.n_gpus == 12
    shrunk = MIXED_16.with_nodes(12)
    assert ep.plan.provenance.tiers["digest"] == tier_fingerprint(shrunk)
    assert ep.plan.provenance.tiers["digest"] != tier_fingerprint(MIXED_16)


def test_configure_on_tiered_spec_scores_compute_aware():
    """configure() on a tiered spec must rank with the compute-aware model:
    the best candidate's recorded latency equals a fresh pipette_latency
    (which prices per-stage compute) of its mapping."""
    spec = MIXED_16
    bw, _ = profile_bandwidth(spec)
    res = configure(W, spec, bw, sa_seconds=60.0, sa_iters=40, sa_topk=2,
                    max_micro=2, seed=2)
    best = res.best
    prof = build_profile(W, spec, best.conf)
    assert best.latency == pipette_latency(best.conf, best.mapping, bw,
                                           prof, spec)
