"""SA worker dedication: move validity (hypothesis property tests),
objective improvement, and end-to-end behaviour on a heterogeneous
cluster."""
import numpy as np
import pytest

from repro.core import (MID_RANGE, Budget, Conf, Workload, build_profile,
                        dedicate_candidates, default_mapping,
                        perm_to_mapping, true_bandwidth_matrix)
from repro.core.annealing import _move_numpy
from repro.core.latency import pipette_latency
from repro.models.config import ModelConfig

# optional dep: skip the module without failing collection; assigning the
# names (instead of `from hypothesis import ...` after a statement) keeps
# every real import at the top of the file (ruff E402)
hyp = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
given, settings = hyp.given, hyp.settings

GPT = ModelConfig(name="g", family="dense", n_layers=24, d_model=1920,
                  n_heads=20, n_kv_heads=20, d_ff=7680, vocab_size=51200)


@settings(max_examples=60, deadline=None)
@given(n=st.integers(4, 128), seed=st.integers(0, 10_000), moves=st.integers(1, 30))
def test_moves_preserve_permutation(n, seed, moves):
    """migration/swap/reverse always yield a bijection (Eq. 2)."""
    rng = np.random.default_rng(seed)
    p = np.arange(n)
    for _ in range(moves):
        kind, pa, pb = (int(v) for v in rng.integers((3, n, n - 1)))
        p, _ = _move_numpy(p, kind, pa, pb + (pb >= pa))
        assert sorted(p.tolist()) == list(range(n))


@settings(max_examples=20, deadline=None)
@given(pp=st.sampled_from([2, 4]), tp=st.sampled_from([1, 2]),
       dp=st.sampled_from([2, 4]))
def test_perm_to_mapping_bijective(pp, tp, dp):
    conf = Conf(pp, tp, dp, 1, 64 * dp)
    perm = np.random.default_rng(0).permutation(conf.n_gpus)
    m = perm_to_mapping(perm, conf)
    assert m.shape == (pp, tp, dp)
    assert sorted(m.reshape(-1).tolist()) == list(range(conf.n_gpus))


def test_sa_improves_on_heterogeneous_cluster():
    spec = MID_RANGE.with_nodes(4)
    w = Workload(GPT, 2048, 128)
    conf = Conf(4, 4, 2, 2, 128)
    bw = true_bandwidth_matrix(spec)
    prof = build_profile(w, spec, conf)
    m0 = default_mapping(conf)
    base = pipette_latency(conf, m0, bw, prof, spec)
    res = dedicate_candidates([conf], [prof], [0], bw, spec,
                              Budget(sa_seconds=1.0, sa_iters=3000), 1)[0]
    assert res.latency <= base * (1 + 1e-9)
    # the best-so-far trace is monotone non-increasing
    vals = [v for _, v in res.trace]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


def test_sa_respects_tp_locality():
    """With the profiled-TP-scale term, SA must not strand a tensor-parallel
    group across nodes (the §IV rationale for intra-server TP)."""
    spec = MID_RANGE.with_nodes(4)
    w = Workload(GPT, 2048, 128)
    conf = Conf(2, 8, 2, 2, 128)
    bw = true_bandwidth_matrix(spec)
    prof = build_profile(w, spec, conf)
    res = dedicate_candidates([conf], [prof], [0], bw, spec,
                              Budget(sa_seconds=1.0, sa_iters=4000), 3)[0]
    for x in range(conf.pp):
        for z in range(conf.dp):
            nodes = {int(res.mapping[x, y, z]) // spec.gpus_per_node
                     for y in range(conf.tp)}
            assert len(nodes) == 1, "TP group split across nodes"
