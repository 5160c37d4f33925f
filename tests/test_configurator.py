"""End-to-end configurator behaviour (Algorithm 1) vs the baselines on the
simulated clusters — the paper's headline claims at test scale."""
import numpy as np
import pytest

from repro.core import (MID_RANGE, Budget, Conf, Workload, amp_configure,
                        amp_latency, build_profile, configure,
                        dedicate_candidates, default_mapping,
                        enumerate_confs, fit_memory_estimator,
                        ground_truth_memory, measure, mlm_configure,
                        pipette_latency, profile_bandwidth,
                        true_bandwidth_matrix, varuna_configure)
from repro.models.config import ModelConfig

GPT = ModelConfig(name="gpt-1.1b", family="dense", n_layers=24, d_model=1920,
                  n_heads=20, n_kv_heads=20, d_ff=7680, vocab_size=51200)
SPEC = MID_RANGE.with_nodes(4)
W = Workload(GPT, 2048, 128)


@pytest.fixture(scope="module")
def bw():
    return true_bandwidth_matrix(SPEC), profile_bandwidth(SPEC)[0]


def test_configure_returns_valid_best(bw):
    bw_true, bw_meas = bw
    res = configure(W, SPEC, bw_meas, sa_seconds=0.08, sa_iters=800)
    best = res.best
    assert best is not None
    assert best.conf.pp * best.conf.tp * best.conf.dp == SPEC.n_gpus
    assert best.conf.valid()
    assert sorted(best.mapping.reshape(-1).tolist()) == \
        list(range(SPEC.n_gpus))
    assert res.ranked == sorted(res.ranked, key=lambda c: c.latency)
    assert res.overhead["n_candidates"] > 10


def test_pipette_not_slower_than_baselines(bw):
    """Measured on the simulator, Pipette's pick must be at least as fast
    as AMP's and Varuna's picks (Fig. 6 direction)."""
    bw_true, bw_meas = bw
    res = configure(W, SPEC, bw_meas, sa_seconds=0.15, sa_iters=2000, seed=2)
    t_ppt = measure(res.best.conf, res.best.mapping, W, SPEC, bw_true)
    amp = amp_configure(W, SPEC)
    t_amp = measure(amp.best.conf, amp.best.mapping, W, SPEC, bw_true)
    vr = varuna_configure(W, SPEC)
    t_vr = measure(vr.best.conf, vr.best.mapping, W, SPEC, bw_true)
    assert t_ppt <= t_amp * 1.02
    assert t_ppt <= t_vr * 1.02


def test_latency_estimator_beats_amp_model(bw):
    """Fig. 5a: MAPE of Pipette's estimator << AMP's model across a diverse
    config sample."""
    bw_true, bw_meas = bw
    errs_p, errs_a = [], []
    from repro.core.memory import enumerate_confs
    sample = [c for c in enumerate_confs(SPEC.n_gpus, W.bs_global,
                                         n_layers=GPT.n_layers)
              if c.bs_micro <= 8][::3][:20]
    for conf in sample:
        prof = build_profile(W, SPEC, conf)
        m = default_mapping(conf)
        truth = measure(conf, m, W, SPEC, bw_true)
        errs_p.append(abs(pipette_latency(conf, m, bw_meas, prof, SPEC)
                          - truth) / truth)
        errs_a.append(abs(amp_latency(conf, m, SPEC, prof) - truth) / truth)
    assert np.mean(errs_p) < np.mean(errs_a)
    assert np.mean(errs_p) < 0.10          # paper: 5.87%


def test_mlm_heuristic_memory_safe(bw):
    bw_true, _ = bw
    res = mlm_configure(W, SPEC, bw_true)
    assert res.best is not None
    assert res.best.conf.tp == SPEC.gpus_per_node
    assert ground_truth_memory(W, res.best.conf, SPEC) <= SPEC.gpu_mem


def test_predict_batch_matches_scalar_bitwise():
    """The batched jitted forward must reproduce the scalar ``predict`` API
    to float32 bit-equality on a large random config sample."""
    est = fit_memory_estimator([W], SPEC, fit_nodes=1, steps=1500,
                               residual=True)
    pool = [c for g in (8, 16, 24, 32, 48, 64) for bsg in (64, 128, 256)
            for c in enumerate_confs(g, bsg, n_layers=GPT.n_layers)
            if c.bs_micro <= 16]
    rng = np.random.default_rng(0)
    confs = [pool[i] for i in rng.choice(len(pool), size=240, replace=False)]
    with np.errstate(over="ignore"):       # extrapolation may saturate exp
        batch = est.predict_batch(W.cfg, confs)
        scalar = np.array([est.predict(W.cfg, c) for c in confs])
    assert batch.shape == (240,)
    assert batch.astype(np.float32).tobytes() == \
        scalar.astype(np.float32).tobytes()


def test_sa_topk_matches_exhaustive_best(bw):
    """Concentrating the SA budget on the top-k pre-scored candidates must
    find the same best as annealing every survivor (small cluster,
    iteration-bound so the SA trajectories are deterministic)."""
    _, bw_meas = bw
    kw = dict(sa_seconds=60.0, sa_iters=250, max_micro=4, seed=3)
    full = configure(W, SPEC, bw_meas, **kw)
    topk = configure(W, SPEC, bw_meas, sa_topk=8, **kw)
    assert topk.best.conf == full.best.conf
    assert topk.best.latency == full.best.latency
    # the knob prunes SA work, not candidates: the ranking stays complete
    assert topk.overhead["n_candidates"] == full.overhead["n_candidates"]


def test_ranked_order_matches_prerefactor_reference(bw):
    """The staged pipeline must rank exactly like a per-candidate loop
    (same confs, bit-equal latencies) for a fixed seed, with and without
    SA dedication: annealing one candidate at a time gives the same
    result as annealing them batched by shape."""
    _, bw_meas = bw
    kw = dict(max_micro=4, seed=5)
    res_sa = configure(W, SPEC, bw_meas, sa_seconds=60.0, sa_iters=120, **kw)
    res_plain = configure(W, SPEC, bw_meas, dedicate=False, **kw)

    cands_sa, cands_plain = [], []
    budget = Budget(sa_seconds=60.0, sa_iters=120)
    for conf in enumerate_confs(SPEC.n_gpus, W.bs_global,
                                n_layers=GPT.n_layers):
        if conf.bs_micro > 4:
            continue
        prof = build_profile(W, SPEC, conf)
        m = default_mapping(conf)
        cands_plain.append((conf, pipette_latency(conf, m, bw_meas, prof,
                                                  SPEC)))
        r = dedicate_candidates([conf], [prof], [0], bw_meas, SPEC, budget,
                                5)[0]
        cands_sa.append((conf, r.latency))
    cands_sa.sort(key=lambda t: t[1])
    cands_plain.sort(key=lambda t: t[1])

    assert [c.conf for c in res_sa.ranked] == [c for c, _ in cands_sa]
    assert [c.latency for c in res_sa.ranked] == [t for _, t in cands_sa]
    assert [c.conf for c in res_plain.ranked] == [c for c, _ in cands_plain]
    assert [c.latency for c in res_plain.ranked] == \
        [t for _, t in cands_plain]


def test_configure_with_memory_estimator_prunes(bw):
    """With a tight memory limit the search must drop OOM configs."""
    _, bw_meas = bw
    from repro.core import fit_memory_estimator
    est = fit_memory_estimator([W], SPEC, fit_nodes=2, steps=2500,
                               residual=True)
    res_all = configure(W, SPEC, bw_meas, dedicate=False)
    res_lim = configure(W, SPEC, bw_meas, estimator=est,
                        mem_limit=SPEC.gpu_mem, dedicate=False)
    assert 0 < res_lim.overhead["n_candidates"] <= \
        res_all.overhead["n_candidates"]
    for c in res_lim.top(10):
        assert ground_truth_memory(W, c.conf, SPEC) <= SPEC.gpu_mem * 1.25
