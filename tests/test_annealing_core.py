"""The SA driver: budget split, wall clock, MovePlan, move semantics,
chain parity.

Covers ``dedicate_candidates``' chain budget split (exact divmod totals,
zero-iteration chains) and the NumPy executor's wall-clock rule, the
host-precomputed :class:`~repro.core.annealing.MovePlan` (determinism,
bounds, exact split, single-island degeneration), the shared move
semantics of the NumPy and JAX executors, and chain-for-chain bit parity
between the two backends at the engine level."""
import time

import numpy as np
import pytest

from repro.core import (MID_RANGE, Budget, ClusterSpec, Conf,
                        DedicationEngine, Workload, build_profile,
                        dedicate_candidates, make_move_plan,
                        perm_to_mapping, profile_bandwidth)
from repro.core.annealing import (_ALPHA, _move_numpy, _run_chain_numpy,
                                  build_islands, coarse_assign,
                                  coarse_orderings)
from repro.configs.gpt_paper import GPT_3_1B

SPEC = ClusterSpec("tiny-2x4", 2, gpus_per_node=4, seed=1)
W = Workload(GPT_3_1B, 2048, 32)
CONF = Conf(2, 2, 2, 2, 32)


@pytest.fixture(scope="module")
def setup():
    bw, _ = profile_bandwidth(SPEC)
    prof = build_profile(W, SPEC, CONF)
    return bw, prof


def _dedicate(bw, prof, seed, **budget):
    return dedicate_candidates([CONF], [prof], [0], bw, SPEC,
                               Budget(**budget), seed)[0]


# ---------------------------------------------------------------------------
# the driver's budget split and wall clock
# ---------------------------------------------------------------------------

def test_multistart_iters_exact_when_chains_exceed_budget(setup):
    """n_chains > sa_iters must NOT run n_chains extra iterations (the
    historical ``max(1, max_iters // n_chains)`` bug)."""
    bw, prof = setup
    res = _dedicate(bw, prof, 0, n_chains=5, sa_seconds=60.0, sa_iters=2)
    assert res.iters == 2
    assert len(res.chain_latencies) == 5


@pytest.mark.parametrize("max_iters,n_chains", [(7, 3), (1, 4), (60, 4),
                                                (9, 9), (10, 1)])
def test_multistart_iters_sum_exactly(setup, max_iters, n_chains):
    bw, prof = setup
    res = _dedicate(bw, prof, 3, n_chains=n_chains, sa_seconds=60.0,
                    sa_iters=max_iters)
    assert res.iters == max_iters


def test_multistart_zero_time_limit_is_score_only():
    """The NumPy executor stops each chain after ``sa_seconds / n_chains``
    of wall clock: a small ``sa_seconds`` against a million moves returns
    within a few multiples of ``sa_seconds``, having run fewer moves than
    ``sa_iters`` (and counting exactly the moves it ran)."""
    spec = MID_RANGE
    conf = Conf(4, 8, 4, 2, 256)
    bw, _ = profile_bandwidth(spec)
    prof = build_profile(Workload(GPT_3_1B, 2048, 256), spec, conf)
    budget = Budget(sa_seconds=0.25, sa_iters=1_000_000, n_chains=3)
    t0 = time.perf_counter()
    res = dedicate_candidates([conf], [prof], [0], bw, spec, budget, 0)[0]
    wall = time.perf_counter() - t0
    assert 0 < res.iters < budget.sa_iters
    assert res.seconds < 4 * budget.sa_seconds
    assert wall < 8 * budget.sa_seconds
    eng = DedicationEngine(conf, bw, prof, spec)
    assert res.latency == eng.score(res.perm)
    assert res.latency <= eng.score(np.arange(conf.n_gpus))


def test_multistart_deterministic_after_fix(setup):
    bw, prof = setup
    kw = dict(n_chains=3, sa_seconds=60.0, sa_iters=50)
    a = _dedicate(bw, prof, 8, **kw)
    b = _dedicate(bw, prof, 8, **kw)
    assert a.latency == b.latency and np.array_equal(a.perm, b.perm)
    assert a.chain_latencies == b.chain_latencies


# ---------------------------------------------------------------------------
# MovePlan
# ---------------------------------------------------------------------------

def test_move_plan_exact_split_and_masks():
    plan = make_move_plan([8], 10, 4, seed=0)
    assert plan.chain_iters.tolist() == [3, 3, 2, 2]
    assert int(plan.chain_iters.sum()) == 10
    assert plan.valid.shape == (4, 3)
    assert (plan.valid.sum(axis=1) == plan.chain_iters).all()


def test_move_plan_zero_budget_chains():
    plan = make_move_plan([8], 2, 5, seed=0)
    assert plan.chain_iters.tolist() == [1, 1, 0, 0, 0]
    assert not plan.valid[2:].any()


def test_move_plan_deterministic_and_bounded():
    sizes = [6, 10, 4]
    a = make_move_plan(sizes, 200, 3, seed=42)
    b = make_move_plan(sizes, 200, 3, seed=42)
    for f in ("kind", "isl", "oa", "ob", "thresh", "probe_kind",
              "probe_isl", "probe_oa", "probe_ob"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    ln = np.asarray(sizes)[a.isl]
    assert (a.oa >= 0).all() and (a.oa < ln).all()
    assert (a.ob >= 0).all() and (a.ob < ln).all()
    assert (a.oa != a.ob).all()
    assert set(np.unique(a.kind)) <= {0, 1, 2}
    assert (a.thresh >= 0).all()


def test_move_plan_single_island_skips_island_draw():
    """One island (flat and degenerate-hierarchical) must consume the same
    RNG stream regardless of how the caller arrived at it — the island
    draw is skipped, so the schedules are identical arrays."""
    a = make_move_plan([16], 50, 2, seed=7)
    b = make_move_plan((16,), 50, 2, seed=7)
    assert (a.isl == 0).all()
    for f in ("kind", "oa", "ob", "thresh"):
        assert np.array_equal(getattr(a, f), getattr(b, f))


def test_move_plan_rejects_degenerate_islands():
    with pytest.raises(ValueError):
        make_move_plan([4, 1], 10, 1, seed=0)
    with pytest.raises(ValueError):
        make_move_plan([8], 10, 0, seed=0)


# ---------------------------------------------------------------------------
# move semantics: NumPy executor == JAX index arithmetic
# ---------------------------------------------------------------------------

def test_move_numpy_semantics():
    perm = np.arange(6)
    mig, t = _move_numpy(perm, 0, 1, 4)      # remove at 1, reinsert at 4
    assert mig.tolist() == [0, 2, 3, 4, 1, 5]
    assert t.tolist() == [1, 2, 3, 4]
    swp, t = _move_numpy(perm, 1, 4, 1)      # order-insensitive positions
    assert swp.tolist() == [0, 4, 2, 3, 1, 5]
    assert sorted(t.tolist()) == [1, 4]
    rev, t = _move_numpy(perm, 2, 1, 4)
    assert rev.tolist() == [0, 4, 3, 2, 1, 5]


def test_moves_match_jax_apply_move():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core.jax_engine import _apply_move

    rng = np.random.default_rng(0)
    pos = jnp.arange(12, dtype=jnp.int32)
    for _ in range(64):
        perm = rng.permutation(12)
        kind = int(rng.integers(3))
        pa = int(rng.integers(12))
        pb = int(rng.integers(11))
        pb += pb >= pa
        want, touched = _move_numpy(perm, kind, pa, pb)
        got = np.asarray(_apply_move(jnp.asarray(perm, dtype=jnp.int32),
                                     pos, kind, pa, pb))
        assert got.tolist() == want.tolist()
        # touched covers every changed position
        changed = np.nonzero(want != perm)[0]
        assert set(changed) <= set(touched.tolist())


# ---------------------------------------------------------------------------
# chain-for-chain backend parity at the engine level
# ---------------------------------------------------------------------------

def test_numpy_and_jax_chains_bit_identical(setup):
    pytest.importorskip("jax")
    from repro.core.jax_engine import JaxDedicationEngine

    bw, prof = setup
    islands = build_islands(SPEC, hierarchical=False)
    plan = make_move_plan([len(i) for i in islands], 30, 3, seed=5)
    eng = DedicationEngine(CONF, bw, prof, SPEC)
    init, offsets, _ = coarse_assign(eng, islands,
                                     coarse_orderings(islands, SPEC))
    np_best = []
    np_perms = []
    np_acc = []
    np_accb = []
    for k in range(3):
        b, p, _, ac, ab = _run_chain_numpy(eng, init, offsets, plan, k,
                                           _ALPHA)
        np_best.append(b)
        np_perms.append(p)
        np_acc.append(ac)
        np_accb.append(ab)

    jeng = JaxDedicationEngine([CONF], [prof], bw, SPEC)
    pas = (offsets[plan.isl] + plan.oa)[None]
    pbs = (offsets[plan.isl] + plan.ob)[None]
    ppas = (offsets[plan.probe_isl] + plan.probe_oa)[None]
    ppbs = (offsets[plan.probe_isl] + plan.probe_ob)[None]
    bests, perms, _, accs, accbs = jeng.anneal(
        init[None], pas, pbs, plan.kind, plan.thresh, plan.valid,
        ppas, ppbs, plan.probe_kind, alpha=_ALPHA)
    for k in range(3):
        assert float(bests[0, k]).hex() == float(np_best[k]).hex(), k
        assert np.array_equal(perms[0, k], np_perms[k]), k
        # the accepted-move counters are part of the parity contract too
        # (the warm-start economy gate reads them from either backend)
        assert int(accs[0, k]) == np_acc[k], k
        assert int(accbs[0, k]) == np_accb[k], k


def test_chain_result_never_worse_than_init(setup):
    bw, prof = setup
    eng = DedicationEngine(CONF, bw, prof, SPEC)
    plan = make_move_plan([CONF.n_gpus], 40, 1, seed=2)
    init = np.arange(CONF.n_gpus)
    b, p, it, acc, acc_best = _run_chain_numpy(eng, init,
                                               np.zeros(1, np.int64),
                                               plan, 0, _ALPHA)
    assert b <= eng.score(init)
    assert b == eng.score(p)        # reported best matches its permutation
    assert it == 40
    assert 0 <= acc_best <= acc <= it
    assert perm_to_mapping(p, CONF).shape == (2, 2, 2)


# ---------------------------------------------------------------------------
# the shared PairCache
# ---------------------------------------------------------------------------

def test_pair_cache_bit_identical_to_masked_construction(setup):
    """PairCache.build's pass-cheap construction (copy + diagonal fill;
    inf canvas + per-node blocks) must reproduce the historical
    full-matrix boolean-mask construction bit for bit."""
    from repro.core import PairCache
    bw, _ = setup
    bw64 = np.asarray(bw, dtype=float)
    g = bw64.shape[0]
    eye_g = np.eye(g, dtype=bool)
    node = np.arange(g) // SPEC.gpus_per_node
    same = node[:, None] == node[None, :]
    want_noself = np.where(eye_g, np.inf, bw64)
    bw_intra = np.where(same & ~eye_g, bw64, np.inf)
    want_sym = np.minimum(bw_intra, bw_intra.T)
    pairs = PairCache.build(bw, SPEC.gpus_per_node)
    assert np.array_equal(pairs.bw, bw64)
    assert np.array_equal(pairs.bw_noself, want_noself)
    assert np.array_equal(pairs.sym_intra, want_sym)


def test_pair_cache_shared_engine_scores_bit_identical(setup):
    bw, prof = setup
    from repro.core import PairCache
    pairs = PairCache.build(bw, SPEC.gpus_per_node)
    eng = DedicationEngine(CONF, bw, prof, SPEC)
    shared = DedicationEngine(CONF, bw, prof, SPEC, pairs=pairs)
    assert shared._bw_noself is pairs.bw_noself     # no rebuild
    rng = np.random.default_rng(0)
    for _ in range(4):
        perm = rng.permutation(CONF.n_gpus)
        assert float(shared.score(perm)).hex() == \
            float(eng.score(perm)).hex()


def test_pair_cache_mismatch_rejected(setup):
    bw, prof = setup
    from repro.core import PairCache
    pairs = PairCache.build(bw, SPEC.gpus_per_node + 1)
    with pytest.raises(ValueError, match="PairCache"):
        DedicationEngine(CONF, bw, prof, SPEC, pairs=pairs)
