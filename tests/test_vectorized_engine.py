"""Vectorized dedication engine: bit-exact equivalence against the
pure-Python reference scorer, incremental delta-scoring correctness, and
multi-start determinism."""
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import (MID_RANGE, Budget, ClusterSpec, Conf, Workload,
                        build_profile, dedicate_candidates,
                        dp_allreduce_times, dp_allreduce_times_ref,
                        make_move_plan, mixed_fleet_spec, pipette_latency,
                        pipette_latency_ref, true_bandwidth_matrix)
from repro.core.annealing import _ALPHA, _move_numpy, _run_chain_numpy
from repro.core.cluster import A100_TIER, V100_TIER
from repro.core.dedication import DedicationEngine, GroupIndex, \
    perm_to_mapping
from repro.core.simulator import hier_allreduce_batch, mapping4
from repro.models.config import ModelConfig

GPT = ModelConfig(name="g", family="dense", n_layers=24, d_model=1920,
                  n_heads=20, n_kv_heads=20, d_ff=7680, vocab_size=51200)


def _random_case(rng, trial):
    """One random (spec, conf, bw, prof, mapping) triple."""
    spec = MID_RANGE.with_nodes(int(rng.choice([1, 2, 4, 8])))
    g = spec.n_gpus
    shapes = [(pp, tp, g // (pp * tp))
              for pp in (1, 2, 4) for tp in (1, 2, 4, 8)
              if g % (pp * tp) == 0]
    pp, tp, dp = shapes[rng.integers(len(shapes))]
    conf = Conf(pp, tp, dp, 2, 16 * dp)
    bw = true_bandwidth_matrix(spec, day=trial % 4)
    prof = build_profile(Workload(GPT, 512, conf.bs_global), spec, conf)
    mapping = perm_to_mapping(rng.permutation(g), conf)
    return spec, conf, bw, prof, mapping


def test_vectorized_latency_matches_reference_exactly():
    """>= 50 random (cluster, conf, mapping) triples, tolerance 0."""
    rng = np.random.default_rng(0)
    for trial in range(60):
        spec, conf, bw, prof, mapping = _random_case(rng, trial)
        vec = pipette_latency(conf, mapping, bw, prof, spec)
        ref = pipette_latency_ref(conf, mapping, bw, prof, spec)
        assert vec == ref, (trial, str(conf), vec - ref)


def test_vectorized_dp_allreduce_matches_reference_exactly():
    rng = np.random.default_rng(1)
    for trial in range(50):
        spec, conf, bw, prof, mapping = _random_case(rng, trial)
        vec = dp_allreduce_times(conf, mapping, bw, prof, spec)
        ref = dp_allreduce_times_ref(conf, mapping, bw, prof, spec)
        assert np.array_equal(vec, ref), (trial, str(conf))


def _allreduce_spec(gpn, tiered, n_gpus=32):
    if tiered:
        return mixed_fleet_spec("two-tier", n_gpus // gpn,
                                (A100_TIER, V100_TIER), gpus_per_node=gpn,
                                seed=7)
    return ClusterSpec("flat", n_gpus // gpn, gpus_per_node=gpn, seed=7)


def _jittered_bw(spec, rng):
    """Per-link noise on top of the node-pair bandwidths, so that which GPU
    represents a node changes the inter-node bottleneck."""
    bw = true_bandwidth_matrix(spec)
    return bw * rng.uniform(0.5, 1.5, bw.shape)


def _row_ref(row, bw, msg, spec):
    """The scalar reference's all-reduce seconds of one group."""
    row = np.asarray(row)
    conf = Conf(1, 1, len(row), 1, len(row))
    return dp_allreduce_times_ref(conf, row.reshape(1, 1, -1), bw,
                                  SimpleNamespace(msg_dp=msg), spec)[0]


@pytest.mark.parametrize("cp", [1, 2])
@pytest.mark.parametrize("tiered", [False, True], ids=["flat", "two_tier"])
@pytest.mark.parametrize("gpn", [1, 2, 8])
def test_dp_allreduce_node_blocks_match_reference(gpn, tiered, cp):
    """Every (pp, tp, dp) shape of a 32-GPU fleet under random permutation
    mappings (ragged per-node counts): per-stage times and per-group rows
    are bit-equal to the scalar reference."""
    spec = _allreduce_spec(gpn, tiered)
    rng = np.random.default_rng(gpn * 10 + cp + 4 * tiered)
    bw = _jittered_bw(spec, rng)
    shapes = [(pp, tp, 32 // (pp * tp * cp)) for pp in (1, 2, 4)
              for tp in (1, 2, 4, 8) if 32 % (pp * tp * cp) == 0]
    for pp, tp, dp in shapes:
        conf = Conf(pp, tp, dp, 1, 4 * dp, cp=cp)
        prof = build_profile(Workload(GPT, 512, conf.bs_global), spec, conf)
        for _ in range(3):
            mapping = perm_to_mapping(rng.permutation(32), conf)
            vec = dp_allreduce_times(conf, mapping, bw, prof, spec)
            ref = dp_allreduce_times_ref(conf, mapping, bw, prof, spec)
            assert np.array_equal(vec, ref), (str(conf), vec - ref)
            ids = mapping4(conf, mapping).reshape(-1, dp)
            rows = hier_allreduce_batch(ids, bw, prof.msg_dp, spec)
            assert [max(t, 0.0) for t in rows] == \
                [_row_ref(r, bw, prof.msg_dp, spec) for r in ids]


# Groups on 4 nodes of 8: rows of one batch differ in node counts, so the
# representatives' block is padded for all but the widest row.
_GROUPS = {
    "inside_one_node": [[0, 1, 2, 3, 4, 5, 6, 7], [15, 9, 13, 8, 10, 11, 12, 14]],
    "one_per_node": [[0, 8, 16, 24], [31, 7, 23, 15]],
    "ragged": [[3, 0, 9, 17, 18, 1, 30], [8, 9, 10, 11, 12, 13, 20],
               [26, 5, 14, 2, 31, 24, 25]],
}


@pytest.mark.parametrize("shape", sorted(_GROUPS))
def test_hier_allreduce_group_shapes_match_reference(shape):
    spec = MID_RANGE.with_nodes(4)
    bw = _jittered_bw(spec, np.random.default_rng(5))
    ids = np.asarray(_GROUPS[shape])
    rows = hier_allreduce_batch(ids, bw, 3e8, spec)
    assert list(rows) == [_row_ref(r, bw, 3e8, spec) for r in ids]


def test_hier_allreduce_zero_link_prices_inf():
    """A zero link on a ring's path prices that group at inf (the scalar
    reference refuses it); groups that avoid the link are unchanged."""
    spec = MID_RANGE.with_nodes(4)
    bw = true_bandwidth_matrix(spec)
    bw[1, 2] = 0.0                      # intra-node, group 0 only
    bw[16, 24] = 0.0                    # representatives of group 1
    ids = np.asarray([[0, 1, 2, 8], [16, 24, 17, 25], [4, 12, 20, 28]])
    rows = hier_allreduce_batch(ids, bw, 3e8, spec)
    assert np.isinf(rows[0]) and np.isinf(rows[1])
    for r in ids[:2]:
        with pytest.raises(ValueError):
            _row_ref(r, bw, 3e8, spec)
    assert rows[2] == _row_ref(ids[2], bw, 3e8, spec)


def test_hier_allreduce_inf_link():
    """An inf link that is nobody's bottleneck changes nothing; a ring
    whose every link is inf prices at 0 (the scalar reference refuses)."""
    spec = MID_RANGE.with_nodes(4)
    bw = true_bandwidth_matrix(spec)
    bw[0, 1] = bw[8, 16] = np.inf
    bw[4, 5] = bw[5, 4] = np.inf
    row = [0, 1, 2, 8, 16]
    assert hier_allreduce_batch(np.asarray([row]), bw, 3e8, spec)[0] == \
        _row_ref(row, bw, 3e8, spec) > 0
    assert hier_allreduce_batch(np.asarray([[4, 5]]), bw, 3e8, spec)[0] == 0
    with pytest.raises(ValueError):
        _row_ref([4, 5], bw, 3e8, spec)


def test_engine_full_score_matches_latency():
    rng = np.random.default_rng(2)
    for trial in range(20):
        spec, conf, bw, prof, _ = _random_case(rng, trial)
        eng = DedicationEngine(conf, bw, prof, spec)
        perm = rng.permutation(conf.n_gpus)
        want = pipette_latency(conf, perm_to_mapping(perm, conf), bw, prof,
                               spec)
        assert eng.score(perm) == want


def test_engine_delta_scoring_matches_full_rescore():
    """Every SA move's incremental score equals a from-scratch evaluation."""
    rng = np.random.default_rng(3)
    for trial in range(10):
        spec, conf, bw, prof, _ = _random_case(rng, trial)
        eng = DedicationEngine(conf, bw, prof, spec)
        perm = rng.permutation(conf.n_gpus)
        eng.score(perm)
        n = conf.n_gpus
        for _ in range(50):
            kind, pa, pb = (int(v) for v in rng.integers((3, n, n - 1)))
            cand, touched = _move_numpy(perm, kind, pa, pb + (pb >= pa))
            val, pending = eng.propose(cand, touched)
            want = pipette_latency(conf, perm_to_mapping(cand, conf), bw,
                                   prof, spec)
            assert val == want, (trial, str(conf), val - want)
            if rng.random() < 0.6:          # mix accepted + rejected moves
                eng.commit(pending)
                perm = cand


def test_group_index_shared_across_microbatch_variants():
    conf_a = Conf(2, 4, 2, 1, 32)
    conf_b = Conf(2, 4, 2, 4, 32)
    idx = GroupIndex.build(conf_a)
    spec = MID_RANGE.with_nodes(2)
    bw = true_bandwidth_matrix(spec)
    for conf in (conf_a, conf_b):
        prof = build_profile(Workload(GPT, 512, conf.bs_global), spec, conf)
        eng = DedicationEngine(conf, bw, prof, spec, index=idx)
        perm = np.random.default_rng(0).permutation(conf.n_gpus)
        want = pipette_latency(conf, perm_to_mapping(perm, conf), bw, prof,
                               spec)
        assert eng.score(perm) == want
    with pytest.raises(ValueError):
        DedicationEngine(Conf(4, 2, 2, 1, 32), bw,
                         build_profile(Workload(GPT, 512, 32), spec,
                                       Conf(4, 2, 2, 1, 32)),
                         spec, index=idx)


def test_engine_anneal_matches_generic_objective_path():
    """A NumPy-executor chain driven by the incremental engine walks the
    exact same trajectory as the same chain driven by full re-scores with
    the pure-Python reference: same MovePlan + bit-equal scores =>
    identical accept/reject decisions."""
    spec = MID_RANGE.with_nodes(4)
    conf = Conf(4, 4, 2, 2, 128)
    bw = true_bandwidth_matrix(spec)
    prof = build_profile(Workload(GPT, 2048, 128), spec, conf)

    class RefScorer:
        def score(self, perm):
            return pipette_latency_ref(conf, perm_to_mapping(perm, conf),
                                       bw, prof, spec)

        def propose(self, cand, touched):
            return self.score(cand), None

        def commit(self, pending):
            pass

    plan = make_move_plan([conf.n_gpus], 800, 1, seed=11)
    init, offsets = np.arange(conf.n_gpus), np.zeros(1, np.int64)
    r_eng = _run_chain_numpy(DedicationEngine(conf, bw, prof, spec), init,
                             offsets, plan, 0, _ALPHA)
    r_ref = _run_chain_numpy(RefScorer(), init, offsets, plan, 0, _ALPHA)
    assert r_eng[0] == r_ref[0]
    assert np.array_equal(r_eng[1], r_ref[1])
    assert r_eng[2:] == r_ref[2:]


def _dedicate(conf, bw, prof, spec, seed, **budget):
    return dedicate_candidates([conf], [prof], [0], bw, spec,
                               Budget(**budget), seed)[0]


def test_multistart_deterministic_and_no_worse_than_single():
    spec = MID_RANGE.with_nodes(4)
    conf = Conf(4, 4, 2, 2, 128)
    bw = true_bandwidth_matrix(spec)
    prof = build_profile(Workload(GPT, 2048, 128), spec, conf)
    kw = dict(n_chains=3, sa_seconds=60.0, sa_iters=900)
    a = _dedicate(conf, bw, prof, spec, 5, **kw)
    b = _dedicate(conf, bw, prof, spec, 5, **kw)
    assert a.latency == b.latency
    assert np.array_equal(a.perm, b.perm)
    assert a.chain_latencies == b.chain_latencies
    assert len(a.chain_latencies) == 3
    assert a.latency == min(a.chain_latencies)
    # chain 0 of the three is the single 300-move chain of the same seed,
    # and the winning chain is at least as good as it
    single = _dedicate(conf, bw, prof, spec, 5, sa_seconds=60.0,
                       sa_iters=300)
    assert a.chain_latencies[0] == single.latency
    assert a.latency <= single.latency
    with pytest.raises(ValueError):
        _dedicate(conf, bw, prof, spec, 5, n_chains=0)
