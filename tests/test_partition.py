"""Non-uniform pipeline partitions + interleaved-1F1B battery.

Four contracts:

1. **solver** — the balanced-partition DP minimizes the max stage cost and
   degenerates to the legacy ceil-first split on uniform cost vectors;
2. **bit-exact legacy path** — ``partition="dp"`` on a uniform-cost model
   (and plain 1F1B everywhere) reproduces the historical plan *bytes*, on
   the default Budget and both SA executors;
3. **parity** — with a real partition and/or ``vpp > 1``, the latency
   reference, the incremental NumPy engine, and the jitted JAX engine all
   score bit-identically;
4. **the win** — on the hybrid (zamba2) and MoE (kimi-k2) configs at
   ``pp = 8`` the DP split beats the honest uniform split in the
   discrete-event simulator.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (ClusterSpec, Conf, Workload, build_profile,
                        enumerate_confs, ground_truth_memory, make_partition,
                        measure, pipette_latency, pipette_latency_ref,
                        profile_bandwidth, resolve_partition,
                        true_bandwidth_matrix, uniform_partition)
from repro.core.partition import Partition, PartitionCache, balanced_partition
from repro.core.simulator import ProfileCache, default_mapping
from repro.configs.kimi_k2_1t_a32b import CONFIG as KIMI
from repro.configs.zamba2_7b import CONFIG as ZAMBA
from repro.models.config import ModelConfig

#: Uniform-cost model: dense, no MoE/hybrid structure, and a vocabulary
#: small enough that the embedding endpoint cost stays below one layer's
#: cost.  16 layers divide evenly at every pp a 16-GPU cluster can
#: enumerate, so the DP solver returns exactly the ceil-first split (and
#: ``resolve_partition`` returns None) for all of them.
DENSE = ModelConfig(name="d16", family="dense", n_layers=16, d_model=256,
                    n_heads=8, n_kv_heads=8, d_ff=1024, vocab_size=512)


# ----------------------------------------------------------------- solver

@pytest.mark.parametrize("L,pp", [(10, 4), (12, 4), (81, 8), (61, 8),
                                  (7, 3), (16, 16), (9, 1)])
def test_uniform_costs_degenerate_to_ceil_first(L, pp):
    part = balanced_partition(np.ones(L), pp)
    assert part == uniform_partition(L, pp)
    assert part.is_uniform()


def test_solver_isolates_heavy_layer():
    # one 5x layer: the DP must give it a small stage instead of pairing
    # it with 2+ neighbours (uniform would put it in a 3-layer stage)
    part = balanced_partition([1, 1, 1, 1, 5, 1, 1, 1, 1, 1], 4)
    sums = part.stage_sums(np.array([1, 1, 1, 1, 5, 1, 1, 1, 1, 1],
                                    float))
    uni = uniform_partition(10, 4)
    uni_sums = uni.stage_sums(np.array([1, 1, 1, 1, 5, 1, 1, 1, 1, 1],
                                       float))
    assert sums.max() < uni_sums.max()
    assert part.sizes[np.argmax(sums)] <= 2


def test_endpoint_costs_shrink_end_stages():
    part = balanced_partition(np.ones(12), 4, head_cost=2.0, tail_cost=2.0)
    sizes = part.sizes
    assert sizes[0] < sizes[1] and sizes[-1] < sizes[1]
    assert sum(sizes) == 12


def test_partition_validation():
    with pytest.raises(ValueError):
        Partition(10, (3, 3, 8, 10))            # not strictly increasing
    with pytest.raises(ValueError):
        Partition(10, (3, 6, 8))                # does not cover n_layers
    with pytest.raises(ValueError):
        Partition(10, (0, 6, 8, 10))            # empty first stage
    with pytest.raises(ValueError):
        balanced_partition(np.ones(4), 5)       # pp > n_layers


def test_partition_json_roundtrip():
    part = make_partition(ZAMBA, 8, 2048, "dp")
    back = Partition.from_json_dict(part.to_json_dict())
    assert back == part and back.sizes == part.sizes


def test_resolve_partition_degenerates_to_none():
    # uniform mode, pp=1, and uniform-cost models all resolve to None —
    # the single predicate the bit-exact legacy path gates on
    assert resolve_partition(ZAMBA, 8, 2048, "uniform") is None
    assert resolve_partition(ZAMBA, 1, 2048, "dp") is None
    for pp in (2, 4, 8, 16):
        assert resolve_partition(DENSE, pp, 128, "dp") is None
    # non-divisible pp: the embed head cost makes a shorter first stage
    # strictly better, so the DP legitimately deviates from ceil-first
    assert resolve_partition(DENSE, 3, 128, "dp") is not None
    assert resolve_partition(ZAMBA, 8, 2048, "dp") is not None


def test_partition_cache_memoizes():
    cache = PartitionCache(ZAMBA, 2048, "dp")
    assert cache.get(8) is cache.get(8)
    assert cache.get(8) == resolve_partition(ZAMBA, 8, 2048, "dp")


# -------------------------------------------------- solver property suite

def test_solver_properties_random_costs():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=60, deadline=None)
    @hyp.given(costs=st.lists(st.floats(0.1, 10.0), min_size=2,
                              max_size=24),
               pp=st.integers(1, 6), seed=st.integers(0, 10))
    def prop(costs, pp, seed):
        hyp.assume(pp <= len(costs))
        c = np.asarray(costs)
        part = balanced_partition(c, pp)
        # structural validity + coverage
        assert part.pp == pp and sum(part.sizes) == len(costs)
        assert all(s >= 1 for s in part.sizes)
        # optimality: never worse than the uniform split's max stage
        uni = uniform_partition(len(costs), pp)
        assert part.stage_sums(c).max() <= uni.stage_sums(c).max() + 1e-12
        # constant vectors degenerate to the ceil-first split exactly
        const = balanced_partition(np.full(len(costs), float(costs[0])), pp)
        assert const == uni

    prop()


# ------------------------------------- legacy path stays bit-exact (e2e)

@pytest.mark.parametrize("backend", [None, "numpy", "jax"])
def test_dp_mode_on_uniform_model_reproduces_legacy_plan_bytes(backend):
    """``SearchSpace(partition="dp")`` on a uniform-cost model resolves
    every candidate's partition to None, so the whole search — enumerate,
    prune, profile, pre-score, SA — replays the historical trajectory and
    the Plan artifact serializes to identical bytes."""
    from repro.core import (Budget, Planner, PipetteStrategy, PlanRequest,
                            SearchSpace)
    spec = ClusterSpec(name="t", n_nodes=4, gpus_per_node=4)
    w = Workload(DENSE, 128, 64)
    bw, _ = profile_bandwidth(spec)
    knobs = {} if backend is None else {"backend": backend}   # None: default
    budget = Budget(sa_seconds=60.0, sa_iters=40, sa_topk=2, **knobs)
    base = Planner(PipetteStrategy()).plan(
        PlanRequest(w, spec, SearchSpace(), budget, seed=3), bw)
    dp = Planner(PipetteStrategy()).plan(
        PlanRequest(w, spec, SearchSpace(partition="dp"), budget, seed=3),
        bw)
    b, d = base.to_json_dict(), dp.to_json_dict()
    assert d["provenance"]["space"]["partition"] == "dp"
    # identical modulo the recorded space knob itself
    d["provenance"]["space"]["partition"] = "uniform"
    assert b == d


def test_explicit_uniform_partition_profile_differs_from_legacy():
    """An *explicit* uniform Partition goes through the per-stage cost
    path (honest comparator); only ``partition is None`` is the legacy
    aggregate — the two must not alias in the ProfileCache."""
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    w = Workload(ZAMBA, 2048, 256)
    conf = Conf(8, 4, 4, 2, 256)
    legacy = build_profile(w, spec, conf)
    honest = build_profile(w, spec, conf,
                           partition=uniform_partition(ZAMBA.n_layers, 8))
    assert legacy.partition is None
    assert honest.partition == uniform_partition(ZAMBA.n_layers, 8).boundaries
    assert honest.stage_work is not None


def test_profile_cache_keys_on_partition_identity():
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    w = Workload(ZAMBA, 2048, 256)
    conf = Conf(8, 4, 4, 2, 256)
    uni_cache = ProfileCache(w, spec)                  # mode "uniform"
    dp_cache = ProfileCache(w, spec, "dp")
    p_uni, p_dp = uni_cache.get(conf), dp_cache.get(conf)
    assert p_uni.partition is None
    assert p_dp.partition == make_partition(ZAMBA, 8, 2048, "dp").boundaries
    assert p_uni.stage_work != p_dp.stage_work
    # bit-identical to the direct constructor with the same partition
    part = dp_cache.partition_for(conf)
    direct = build_profile(w, spec, conf, partition=part)
    assert p_dp == direct
    # memoized: same object back, including across dp variants
    assert dp_cache.get(conf) is p_dp
    assert dp_cache.get(dataclasses.replace(conf, dp=8, tp=1)) is not p_dp


# ------------------------------------------------- scorer parity (bitwise)

@pytest.mark.parametrize("vpp", [1, 2])
def test_numpy_jax_ref_parity_nonuniform(vpp):
    from repro.core.dedication import DedicationEngine
    from repro.core.jax_engine import JaxDedicationEngine
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    w = Workload(ZAMBA, 2048, 256)
    bw = true_bandwidth_matrix(spec)
    conf = Conf(8, 4, 4, 2, 256, vpp=vpp)
    part = make_partition(ZAMBA, 8 * vpp, 2048, "dp")
    prof = build_profile(w, spec, conf, partition=part)
    npe = DedicationEngine(conf, bw, prof, spec)
    jxe = JaxDedicationEngine([conf], [prof], bw, spec)
    rng = np.random.default_rng(0)
    m4 = default_mapping(conf).reshape(conf.pp, conf.tp, conf.cp, conf.dp)
    ref = pipette_latency_ref(conf, m4, bw, prof, spec)
    fast = pipette_latency(conf, m4, bw, prof, spec)
    assert float(ref).hex() == float(fast).hex()
    for _ in range(4):
        perm = rng.permutation(spec.n_gpus)
        a, b = npe.score(perm), jxe.score(perm, 0)
        assert float(a).hex() == float(b).hex()


def test_vpp1_formula_reduces_to_plain():
    """With vpp=1 the interleaved formula must be the plain hetero
    combine; build the same profile both ways and compare."""
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    w = Workload(ZAMBA, 2048, 256)
    bw = true_bandwidth_matrix(spec)
    conf = Conf(8, 4, 4, 2, 256)
    part = make_partition(ZAMBA, 8, 2048, "dp")
    prof = build_profile(w, spec, conf, partition=part)
    m4 = default_mapping(conf).reshape(conf.pp, conf.tp, conf.cp, conf.dp)
    lat = pipette_latency(conf, m4, bw, prof, spec)
    assert np.isfinite(lat) and lat > 0


# ----------------------------------------------- vpp schedule + enumerate

def test_vpp_schedulability():
    # interleaving needs pp > 1 and n_mb divisible by pp
    assert not Conf(1, 4, 4, 2, 256, vpp=2).schedulable()
    ok = Conf(8, 4, 4, 2, 256, vpp=2)      # n_mb = 32, 32 % 8 == 0
    assert ok.schedulable() and ok.schedule == "interleaved-1f1b"
    assert Conf(8, 4, 4, 2, 256).schedule == "1f1b"
    bad = Conf(8, 4, 4, 2, 96, vpp=2)      # n_mb = 12, 12 % 8 != 0
    assert not bad.schedulable()


def test_enumerate_confs_appends_vpp_variants():
    base = enumerate_confs(128, 256, n_layers=32)
    vpp = enumerate_confs(128, 256, n_layers=32, max_vpp=2)
    assert [c for c in vpp if c.vpp == 1] == base     # order preserved
    extra = [c for c in vpp if c.vpp > 1]
    assert extra and all(c.pp > 1 and c.schedulable() for c in extra)
    assert all(c.pp * c.vpp <= 32 for c in extra)


def test_interleaved_simulator_runs_and_is_deterministic():
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    w = Workload(ZAMBA, 2048, 256)
    bw = true_bandwidth_matrix(spec)
    conf = Conf(8, 4, 4, 2, 256, vpp=2)
    part = make_partition(ZAMBA, 16, 2048, "dp")
    m = default_mapping(conf)
    a = measure(conf, m, w, spec, bw, seed=1, partition=part)
    b = measure(conf, m, w, spec, bw, seed=1, partition=part)
    assert float(a).hex() == float(b).hex()
    assert np.isfinite(a) and a > 0


# --------------------------------------------------------- memory (worst
# stage) and the residual-key regression

def test_memory_worst_stage_and_residual_keying():
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    w = Workload(ZAMBA, 2048, 64)
    conf = Conf(8, 4, 4, 2, 64)
    m_dp = ground_truth_memory(w, conf, spec,
                               partition=make_partition(ZAMBA, 8, 2048,
                                                        "dp"))
    m_uni = ground_truth_memory(w, conf, spec,
                                partition=uniform_partition(81, 8))
    # different partitions must not alias each other's residual cache
    assert m_dp != m_uni
    # the balanced split's worst stage is no heavier than uniform's
    assert m_dp <= m_uni
    # vpp adds framework overhead for the extra model chunks
    conf_v = dataclasses.replace(conf, vpp=2)
    m_vpp = ground_truth_memory(w, conf_v, spec,
                                partition=make_partition(ZAMBA, 16, 2048,
                                                         "dp"))
    assert np.isfinite(m_vpp) and m_vpp > 0


# ------------------------------------------------------------ the win

@pytest.mark.parametrize("cfg", [ZAMBA, KIMI], ids=lambda c: c.name)
def test_dp_beats_uniform_simulated_at_pp8(cfg):
    """The headline gate: on the hybrid and MoE configs the DP split must
    be no slower than the *honest* uniform split (same per-stage cost
    model, uniform boundaries) in the discrete-event simulator."""
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    w = Workload(cfg, 2048, 64)
    conf = Conf(8, 4, 4, 2, 64)
    bw = true_bandwidth_matrix(spec)
    m = default_mapping(conf)
    part_u = uniform_partition(cfg.n_layers, 8)
    part_dp = make_partition(cfg, 8, 2048, "dp")
    assert part_dp != part_u
    sim_u = measure(conf, m, w, spec, bw, seed=1, partition=part_u)
    sim_dp = measure(conf, m, w, spec, bw, seed=1, partition=part_dp)
    assert sim_dp <= sim_u


def test_dp_beats_uniform_estimated_at_pp8():
    """Same direction in the first-order estimator (the search objective):
    a balanced split can only lower the paced ``c_max`` term."""
    spec = ClusterSpec(name="t", n_nodes=16, gpus_per_node=8)
    bw = true_bandwidth_matrix(spec)
    for cfg in (ZAMBA, KIMI):
        w = Workload(cfg, 2048, 64)
        conf = Conf(8, 4, 4, 2, 64)
        m4 = default_mapping(conf).reshape(conf.pp, conf.tp, conf.cp,
                                           conf.dp)
        p_u = build_profile(w, spec, conf,
                            partition=uniform_partition(cfg.n_layers, 8))
        p_dp = build_profile(w, spec, conf,
                             partition=make_partition(cfg, 8, 2048, "dp"))
        lat_u = pipette_latency(conf, m4, bw, p_u, spec)
        lat_dp = pipette_latency(conf, m4, bw, p_dp, spec)
        assert lat_dp <= lat_u
