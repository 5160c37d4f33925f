"""4D (context-parallel) configurator: enumeration gates, cp=1
bit-exactness, cp>1 estimator/engine/simulator equivalences, and the
long-context scenario the 3D space cannot serve."""
import numpy as np
import pytest

from repro.core import (MID_RANGE, Conf, Workload, build_profile, configure,
                        default_mapping, dp_allreduce_times,
                        dp_allreduce_times_ref, enumerate_confs,
                        fit_memory_estimator, ground_truth_memory, measure,
                        mixed_fleet_spec, pipette_latency,
                        pipette_latency_ref, profile_bandwidth,
                        true_bandwidth_matrix)
from repro.core.cluster import A100_TIER, V100_TIER
from repro.core.annealing import _move_numpy
from repro.core.dedication import (DedicationEngine, GroupIndex,
                                   perm_to_mapping)
from repro.core.latency import _t_dp_first_stage, default_mapping_latencies
from repro.core.simulator import ProfileCache, mapping4
from repro.configs.gemma3_12b import CONFIG as GEMMA3
from repro.models.config import ModelConfig

GPT = ModelConfig(name="g", family="dense", n_layers=24, d_model=1920,
                  n_heads=20, n_kv_heads=20, d_ff=7680, vocab_size=51200)
SPEC = MID_RANGE.with_nodes(4)
SEQ = 2048


# ---------------------------------------------------------------------------
# enumeration: schedule validity (the bugfix) + the 4D gates
# ---------------------------------------------------------------------------

def test_enumerate_drops_unschedulable_confs():
    """The motivating bug: at G=8, bs=8 the unfiltered space contains 10
    configurations with n_mb < pp that memory-efficient 1F1B cannot fill."""
    loose = enumerate_confs(8, 8, strict=False)
    strict = enumerate_confs(8, 8)
    bad = [c for c in loose if c.n_mb < c.pp]
    assert len(bad) == 10
    assert len(loose) - len(strict) == 10
    assert strict == [c for c in loose if c.n_mb >= c.pp]


def test_every_enumerated_conf_valid_and_schedulable():
    """Property (non-hypothesis twin of the test_memory_estimator one):
    every conf from a strict enumeration is valid and 1F1B-schedulable,
    including in 4D."""
    for g, bs, max_cp in [(8, 8, 1), (16, 64, 1), (32, 128, 4),
                          (64, 256, 8), (24, 48, 2)]:
        confs = enumerate_confs(g, bs, n_layers=32, max_cp=max_cp, seq=SEQ)
        assert confs
        for c in confs:
            assert c.pp * c.tp * c.cp * c.dp == g
            assert c.valid() and c.schedulable()
            assert c.n_mb >= c.pp
            assert SEQ % c.cp == 0
        assert len({(c.pp, c.tp, c.cp, c.dp, c.bs_micro)
                    for c in confs}) == len(confs)


def test_enumerate_cp_requires_seq():
    """cp > 1 without a sequence length (or with a non-dividing one) is
    never emitted: ring attention needs seq % cp == 0."""
    assert all(c.cp == 1 for c in enumerate_confs(16, 16, max_cp=4))
    confs = enumerate_confs(16, 16, max_cp=4, seq=6)
    assert {c.cp for c in confs} <= {1, 2}      # 4 does not divide 6


def test_conf_valid_rejects_zero_microbatches():
    assert not Conf(1, 1, 1, 4, 0).valid()          # n_mb == 0
    assert not Conf(1, 1, 2, 1, 3).valid()          # dp does not divide
    assert Conf(1, 1, 1, 1, 1).valid()
    assert not Conf(4, 1, 1, 1, 2).schedulable()    # n_mb=2 < pp=4
    assert Conf(2, 1, 1, 1, 2).schedulable()


def test_cp1_enumeration_is_the_3d_space():
    """max_cp=1 (the default) must reproduce the 3D enumeration exactly —
    same confs, same order — whether or not seq is supplied."""
    a = enumerate_confs(32, 64, n_layers=24)
    b = enumerate_confs(32, 64, n_layers=24, max_cp=1, seq=SEQ)
    assert a == b
    assert all(c.cp == 1 for c in a)


# ---------------------------------------------------------------------------
# cp > 1 model equivalences (vectorized == reference == engine)
# ---------------------------------------------------------------------------

def _cp_cases():
    return [Conf(2, 2, 2, 2, 64, cp=4), Conf(1, 4, 2, 1, 16, cp=4),
            Conf(4, 2, 1, 2, 32, cp=4), Conf(2, 4, 2, 2, 32, cp=2),
            Conf(1, 1, 4, 1, 8, cp=8)]


def test_cp_latency_vectorized_matches_reference_exactly():
    rng = np.random.default_rng(0)
    bw = true_bandwidth_matrix(SPEC)
    for conf in _cp_cases():
        prof = build_profile(Workload(GPT, SEQ, conf.bs_global), SPEC, conf)
        assert prof.t_cp_fwd > 0 and prof.msg_cp > 0
        for _ in range(8):
            m = perm_to_mapping(rng.permutation(conf.n_gpus), conf)
            assert m.shape == (conf.pp, conf.tp, conf.cp, conf.dp)
            vec = pipette_latency(conf, m, bw, prof, SPEC)
            ref = pipette_latency_ref(conf, m, bw, prof, SPEC)
            assert vec == ref, (str(conf), vec - ref)
            assert np.array_equal(
                dp_allreduce_times(conf, m, bw, prof, SPEC),
                dp_allreduce_times_ref(conf, m, bw, prof, SPEC))


def test_cp_engine_score_and_delta_match_latency():
    """Full scores and incremental move re-scores of the 4D engine are
    bit-equal to pipette_latency, across accepted and rejected moves."""
    rng = np.random.default_rng(1)
    bw = true_bandwidth_matrix(SPEC)
    for conf in _cp_cases():
        prof = build_profile(Workload(GPT, SEQ, conf.bs_global), SPEC, conf)
        idx = GroupIndex.build(conf)
        assert idx.pos_cp is not None and idx.pos_cp.shape == \
            (conf.pp * conf.tp * conf.dp, conf.cp)
        eng = DedicationEngine(conf, bw, prof, SPEC, index=idx)
        perm = rng.permutation(conf.n_gpus)
        assert eng.score(perm) == pipette_latency(
            conf, perm_to_mapping(perm, conf), bw, prof, SPEC)
        n = conf.n_gpus
        for _ in range(60):
            kind, pa, pb = (int(v) for v in rng.integers((3, n, n - 1)))
            cand, touched = _move_numpy(perm, kind, pa, pb + (pb >= pa))
            val, pending = eng.propose(cand, touched)
            want = pipette_latency(conf, perm_to_mapping(cand, conf), bw,
                                   prof, SPEC)
            assert val == want, (str(conf), val - want)
            if rng.random() < 0.6:
                eng.commit(pending)
                perm = cand


def test_cp_default_mapping_latencies_match_scalar():
    bw = true_bandwidth_matrix(SPEC)
    w = Workload(GPT, SEQ, 64)
    confs = [c for c in enumerate_confs(SPEC.n_gpus, w.bs_global,
                                        n_layers=GPT.n_layers, max_cp=4,
                                        seq=SEQ) if c.bs_micro <= 4]
    assert any(c.cp > 1 for c in confs)
    cache = ProfileCache(w, SPEC)
    profiles = [cache.get(c) for c in confs]
    batch = default_mapping_latencies(confs, profiles, bw, SPEC)
    for i, (conf, prof) in enumerate(zip(confs, profiles)):
        assert batch[i] == pipette_latency(conf, default_mapping(conf), bw,
                                           prof, SPEC), str(conf)


def test_default_mapping_latencies_match_reference_on_512_gpus():
    """A 64-node x 8 two-tier fleet, where stage 0's DP groups span many
    nodes: the pre-score's stage-0-only reduction over node blocks is
    bit-equal to the pure-Python reference for every enumerated conf."""
    spec = mixed_fleet_spec("two-tier-512", 64, (A100_TIER, V100_TIER),
                            seed=3)
    bw = true_bandwidth_matrix(spec)
    w = Workload(GPT, SEQ, 512)
    confs = enumerate_confs(spec.n_gpus, w.bs_global, n_layers=GPT.n_layers,
                            max_tp=8, max_cp=2, seq=SEQ)
    assert any(c.cp > 1 for c in confs) and any(c.dp >= 64 for c in confs)
    cache = ProfileCache(w, spec)
    profiles = [cache.get(c) for c in confs]
    batch = default_mapping_latencies(confs, profiles, bw, spec)
    for i, (conf, prof) in enumerate(zip(confs, profiles)):
        m = default_mapping(conf)
        assert batch[i] == pipette_latency_ref(conf, m, bw, prof, spec), \
            str(conf)
        assert _t_dp_first_stage(conf, m, bw, prof, spec) == \
            dp_allreduce_times_ref(conf, m, bw, prof, spec)[0], str(conf)


def test_mapping4_accepts_legacy_and_4d_shapes():
    c3 = Conf(2, 2, 2, 1, 8)
    m3 = default_mapping(c3)
    assert m3.shape == (2, 2, 2)
    assert mapping4(c3, m3).shape == (2, 2, 1, 2)
    assert np.array_equal(mapping4(c3, m3)[:, :, 0, :], m3)
    c4 = Conf(2, 2, 2, 1, 8, cp=2)
    m4 = default_mapping(c4)
    assert m4.shape == (2, 2, 2, 2)
    assert sorted(m4.reshape(-1).tolist()) == list(range(16))
    assert np.array_equal(mapping4(c4, m4), m4)


def test_cp_profile_shards_sequence():
    """cp shrinks per-rank compute/messages; the KV-exchange term appears
    only for cp > 1 and grows with the ring size."""
    w = Workload(GPT, SEQ, 64)
    p1 = build_profile(w, SPEC, Conf(2, 2, 2, 2, 64))
    p2 = build_profile(w, SPEC, Conf(2, 2, 1, 2, 64, cp=2))
    p4 = build_profile(w, SPEC, Conf(2, 2, 1, 2, 64, cp=4))
    assert p1.t_cp_fwd == 0.0 and p1.msg_cp == 0.0
    assert p2.msg_pp == p1.msg_pp / 2
    assert p2.c_fwd < p1.c_fwd
    assert p2.t_cp_fwd > 0
    assert p4.t_cp_fwd > p2.t_cp_fwd        # more ring steps
    assert p4.msg_cp < p2.msg_cp            # smaller KV blocks


# ---------------------------------------------------------------------------
# memory: cp terms + the with_cp estimator contract
# ---------------------------------------------------------------------------

def test_cp_shrinks_activation_memory():
    w = Workload(GPT, SEQ, 64)
    base = ground_truth_memory(w, Conf(2, 2, 2, 2, 64), SPEC)
    cp2 = ground_truth_memory(w, Conf(2, 2, 1, 2, 64, cp=2), SPEC)
    assert cp2 < base


def test_3d_estimator_refuses_cp_configs():
    w = Workload(GPT, 1024, 32)
    est = fit_memory_estimator([w], MID_RANGE, fit_nodes=1, steps=300)
    assert not est.with_cp
    with pytest.raises(ValueError, match="cp"):
        est.predict_batch(w.cfg, [Conf(1, 2, 1, 1, 32, cp=4)])


# ---------------------------------------------------------------------------
# the headline scenario: long context is infeasible in 3D, feasible in 4D
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def long_ctx():
    cfg = GEMMA3.reduced()
    spec = MID_RANGE.with_nodes(2)          # 16 GPUs x 32 GB
    w = Workload(cfg, 65536, 2)             # gemma3-class long context
    return cfg, spec, w


def test_long_context_infeasible_in_3d(long_ctx):
    cfg, spec, w = long_ctx
    confs = enumerate_confs(spec.n_gpus, w.bs_global,
                            max_tp=spec.gpus_per_node,
                            n_layers=cfg.n_layers, seq=w.seq)
    assert confs                              # the space is non-empty...
    assert all(ground_truth_memory(w, c, spec) > spec.gpu_mem
               for c in confs)                # ...but everything OOMs


def test_long_context_feasible_with_cp(long_ctx):
    cfg, spec, w = long_ctx
    confs = enumerate_confs(spec.n_gpus, w.bs_global,
                            max_tp=spec.gpus_per_node,
                            n_layers=cfg.n_layers, max_cp=8, seq=w.seq)
    feas = [c for c in confs
            if ground_truth_memory(w, c, spec) <= spec.gpu_mem]
    assert feas
    assert all(c.cp > 1 for c in feas)


def test_configure_4d_finds_long_context_config(long_ctx):
    """End-to-end acceptance: the 4D search (cp-aware estimator included)
    returns a memory-feasible recommendation where the 3D search returns
    nothing."""
    cfg, spec, w = long_ctx
    ws = [Workload(cfg, w.seq, bsg) for bsg in (2, 4, 8)]
    est = fit_memory_estimator(ws, spec, fit_nodes=2, steps=2500,
                               residual=True, max_cp=8)
    assert est.with_cp
    bw, _ = profile_bandwidth(spec)
    kw = dict(estimator=est, max_tp=spec.gpus_per_node,
              sa_seconds=0.05, sa_iters=300)
    res3 = configure(w, spec, bw, **kw)
    assert res3.best is None                  # 3D: everything pruned
    res4 = configure(w, spec, bw, max_cp=8, **kw)
    assert res4.best is not None
    assert res4.best.conf.cp > 1
    assert ground_truth_memory(w, res4.best.conf, spec) <= spec.gpu_mem
    assert res4.best.conf.n_gpus == spec.n_gpus
    assert sorted(res4.best.mapping.reshape(-1).tolist()) == \
        list(range(spec.n_gpus))
    # the recommendation actually runs on the simulated cluster
    t = measure(res4.best.conf, res4.best.mapping, w, spec,
                true_bandwidth_matrix(spec))
    assert np.isfinite(t) and t > 0
