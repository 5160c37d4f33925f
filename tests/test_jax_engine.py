"""JAX scorer equivalence: bit parity with the NumPy engine and kernels.

On CPU the JAX engine's contract is *bit*-equality with
:class:`~repro.core.dedication.DedicationEngine` (not a tolerance): f64
under scoped x64, matching reduction order, and a replica of NumPy's
pairwise summation for the tiered per-stage sum.  Checked across uniform,
mixed-tier and degraded-host specs, against the vectorized engine, the
batched ``pipette_latency`` and the pure-Python reference.  The float32
Pallas group-reduce kernels that the TPU runs are checked in interpret
mode against their jnp references, and the scores they give against the
reference within ``TPU_REL_TOL``."""
import numpy as np
import pytest

from repro.core import (MID_RANGE, MIXED_A100_V100, MID_RANGE_DEGRADED,
                        DedicationEngine, Workload, build_profile,
                        perm_to_mapping, pipette_latency,
                        pipette_latency_ref, profile_bandwidth)
from repro.core.memory import enumerate_confs
from repro.core.simulator import ProfileCache
from repro.configs.gpt_paper import GPT_3_1B

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.jax_engine import (TPU_REL_TOL,  # noqa: E402
                                   JaxDedicationEngine, kernels_mode,
                                   np_pairwise_sum)
from repro.kernels.group_reduce import (group_max,  # noqa: E402
                                        group_max_ref, group_min_scale,
                                        group_min_scale_ref)

W = Workload(GPT_3_1B, 2048, 256)
SPECS = {"uniform": MID_RANGE, "mixed": MIXED_A100_V100,
         "degraded": MID_RANGE_DEGRADED}


def _confs(spec, k=3):
    """A few 4D shapes exercising every term: pp>1, tp>1, cp>1 included."""
    out = [c for c in enumerate_confs(spec.n_gpus, W.bs_global,
                                      n_layers=GPT_3_1B.n_layers, max_cp=2,
                                      seq=W.seq)
           if c.pp > 1 and c.tp > 1]
    out.sort(key=lambda c: (c.cp == 1, c.pp, c.tp))   # cp>1 first
    return out[:k]


# ---------------------------------------------------------------------------
# the NumPy pairwise-sum replica
# ---------------------------------------------------------------------------

def test_np_pairwise_sum_bit_exact_vs_np_sum():
    rng = np.random.default_rng(0)
    for n in list(range(1, 40)) + [63, 64, 65, 127, 128, 129, 200, 300]:
        x = rng.standard_normal(n) * rng.uniform(1e-3, 1e3)
        assert float(np_pairwise_sum(x, n)).hex() == \
            float(np.sum(x)).hex(), n


def test_np_pairwise_sum_traced_matches_host():
    x = np.random.default_rng(1).standard_normal(37)
    with jax.enable_x64(True):
        got = float(jax.jit(lambda v: np_pairwise_sum(v, 37))(jnp.asarray(x)))
    assert got.hex() == float(np.sum(x)).hex()


# ---------------------------------------------------------------------------
# full-score equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(SPECS))
def test_jax_score_bit_identical_to_numpy_engine(kind):
    spec = SPECS[kind]
    bw, _ = profile_bandwidth(spec)
    confs = _confs(spec)
    cache = ProfileCache(W, spec)
    profs = [cache.get(c) for c in confs]
    rng = np.random.default_rng(5)
    # group by shape: one engine per shape, matching the driver
    by_shape = {}
    for c, p in zip(confs, profs):
        by_shape.setdefault((c.pp, c.tp, c.cp, c.dp), []).append((c, p))
    for shape, group in by_shape.items():
        cs = [c for c, _ in group]
        ps = [p for _, p in group]
        jeng = JaxDedicationEngine(cs, ps, bw, spec)
        for ci, (conf, prof) in enumerate(group):
            eng = DedicationEngine(conf, bw, prof, spec)
            for _ in range(4):
                perm = rng.permutation(spec.n_gpus)
                want = eng.score(perm)
                got = jeng.score(perm, ci)
                assert float(got).hex() == float(want).hex(), (shape, ci)
                # and both equal the batch latency evaluator
                lat = pipette_latency(conf, perm_to_mapping(perm, conf),
                                      bw, prof, spec)
                assert float(lat).hex() == float(want).hex()


@pytest.mark.parametrize("kind", sorted(SPECS))
def test_jax_score_matches_pure_python_reference(kind):
    """The pure-Python reference recomputes Eq. 3-6 scalar by scalar, so
    parity is a pinned tolerance, not bitwise."""
    spec = SPECS[kind]
    bw, _ = profile_bandwidth(spec)
    conf = _confs(spec, 1)[0]
    prof = build_profile(W, spec, conf)
    jeng = JaxDedicationEngine([conf], [prof], bw, spec)
    rng = np.random.default_rng(9)
    for _ in range(3):
        perm = rng.permutation(spec.n_gpus)
        ref = pipette_latency_ref(conf, perm_to_mapping(perm, conf), bw,
                                  prof, spec)
        assert jeng.score(perm) == pytest.approx(ref, rel=1e-12)


def test_compute_blind_engine_matches():
    spec = MIXED_A100_V100
    bw, _ = profile_bandwidth(spec)
    conf = _confs(spec, 1)[0]
    prof = build_profile(W, spec, conf)
    eng = DedicationEngine(conf, bw, prof, spec, compute_aware=False)
    jeng = JaxDedicationEngine([conf], [prof], bw, spec,
                               compute_aware=False)
    perm = np.random.default_rng(2).permutation(spec.n_gpus)
    assert float(jeng.score(perm)).hex() == float(eng.score(perm)).hex()


def test_score_batch_matches_scalar_scores():
    """One vmapped dispatch over a batch of permutations equals the
    per-permutation path bitwise (the --huge throughput gate's contract)."""
    spec = MIXED_A100_V100
    bw, _ = profile_bandwidth(spec)
    confs = _confs(spec)
    by_shape = {}
    for c in confs:
        by_shape.setdefault((c.pp, c.tp, c.cp, c.dp), []).append(c)
    cs = next(iter(by_shape.values()))
    cache = ProfileCache(W, spec)
    ps = [cache.get(c) for c in cs]
    jeng = JaxDedicationEngine(cs, ps, bw, spec)
    rng = np.random.default_rng(3)
    perms = np.stack([rng.permutation(spec.n_gpus) for _ in range(5)])
    for ci, (conf, prof) in enumerate(zip(cs, ps)):
        eng = DedicationEngine(conf, bw, prof, spec)
        batch = jeng.score_batch(perms, ci)
        assert batch.shape == (5,)
        for r, perm in enumerate(perms):
            assert float(batch[r]).hex() == float(eng.score(perm)).hex()
            assert float(batch[r]).hex() == \
                float(jeng.score(perm, ci)).hex()


def test_shared_pairs_and_device_pairs_do_not_change_scores():
    """Engines fed a prebuilt PairCache / a sibling's device buffers (the
    dedicate_candidates sharing path) score bit-identically to
    self-building engines."""
    from repro.core import PairCache
    spec = MIXED_A100_V100
    bw, _ = profile_bandwidth(spec)
    conf = _confs(spec, 1)[0]
    prof = build_profile(W, spec, conf)
    pairs = PairCache.build(bw, spec.gpus_per_node)
    own = JaxDedicationEngine([conf], [prof], bw, spec)
    shared = JaxDedicationEngine([conf], [prof], bw, spec, pairs=pairs,
                                 device_pairs=own.device_pairs)
    assert shared.device_pairs is own.device_pairs
    eng_own = DedicationEngine(conf, bw, prof, spec)
    eng_shared = DedicationEngine(conf, bw, prof, spec, pairs=pairs)
    perm = np.random.default_rng(4).permutation(spec.n_gpus)
    want = float(eng_own.score(perm)).hex()
    assert float(eng_shared.score(perm)).hex() == want
    assert float(own.score(perm)).hex() == want
    assert float(shared.score(perm)).hex() == want


# ---------------------------------------------------------------------------
# float32 Pallas kernels (interpret mode on CPU) vs float64 jnp references
# ---------------------------------------------------------------------------

F32_EPS = 2.0 ** -24        # unit roundoff of float32


def _random_sub(rng, n, m):
    sub = rng.uniform(0.5, 300.0, size=(n, m, m)) * 1e9
    di = np.arange(m)
    sub[:, di, di] = np.inf                     # self links masked upstream
    sub[rng.integers(n), 0, min(1, m - 1)] = 0.0  # degenerate link
    return sub


@pytest.mark.parametrize("n,m", [(1, 2), (7, 4), (128, 8), (130, 2),
                                 (1300, 4)])
def test_group_min_scale_interpret_matches_ref(n, m):
    sub = _random_sub(np.random.default_rng(n * 31 + m), n, m)
    with jax.enable_x64(True):
        ref = np.asarray(group_min_scale_ref(jnp.asarray(sub), 25e9))
        pal = np.asarray(group_min_scale(jnp.asarray(sub), 25e9,
                                         interpret=True))
        # on float32 operands the group minima are exact, so only the
        # float32 divide of ref_bw separates kernel and reference
        sub32 = sub.astype(np.float32)
        ref32 = np.asarray(group_min_scale_ref(jnp.asarray(sub32),
                                               np.float32(25e9)))
        pal32 = np.asarray(group_min_scale(jnp.asarray(sub32), 25e9,
                                           interpret=True))
    assert ref.shape == pal.shape == (n,) and pal.dtype == np.float32
    assert ((ref == 1.0) == (pal == 1.0)).all()   # same degenerate guard
    np.testing.assert_allclose(pal, ref, rtol=3 * F32_EPS, atol=0)
    assert (pal32 == ref32).all()


@pytest.mark.parametrize("n,m", [(1, 3), (9, 16), (128, 4), (257, 8),
                                 (5, 2048)])
def test_group_max_interpret_matches_ref(n, m):
    vals = np.random.default_rng(n * 17 + m).uniform(1.0, 3.0, size=(n, m))
    with jax.enable_x64(True):
        pal = np.asarray(group_max(jnp.asarray(vals), interpret=True))
    # rounding is monotonic: the float32 max is the rounded float64 max
    assert (pal == vals.max(axis=1).astype(np.float32)).all()


def test_engine_kernel_modes_agree():
    """The interpreted float32 kernels give the score the reference path
    gives, within the TPU tolerance."""
    spec = MIXED_A100_V100
    bw, _ = profile_bandwidth(spec)
    conf = _confs(spec, 1)[0]
    prof = build_profile(W, spec, conf)
    rng = np.random.default_rng(3)
    ref = JaxDedicationEngine([conf], [prof], bw, spec)
    pal = JaxDedicationEngine([conf], [prof], bw, spec, kernels="interpret")
    for _ in range(4):
        perm = rng.permutation(spec.n_gpus)
        assert pal.score(perm) == pytest.approx(ref.score(perm),
                                                rel=TPU_REL_TOL)


def test_kernels_mode_resolution():
    assert kernels_mode("pallas") == "pallas"
    assert kernels_mode("interpret") == "interpret"
    assert kernels_mode("auto") == ("pallas" if jax.default_backend() == "tpu"
                                    else "ref")
    with pytest.raises(ValueError):
        kernels_mode("ref")
