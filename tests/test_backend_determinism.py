"""Backend determinism: byte-identical Plan JSON across SA backends.

The unified SA core promises that ``backend="numpy"`` and
``backend="jax"`` are the *same algorithm* with two executors: given one
``PlanRequest`` and seed, the serialized Plan artifacts must be
byte-identical except for the single ``provenance.budget.backend`` field
that legitimately records which executor ran.  Re-running either backend
must also reproduce its own bytes exactly.  Byte identity is the CPU
contract; the float32 Pallas group-reduce kernels that the TPU runs
(interpret mode here) must give the same plan within the TPU tolerance."""
import collections
import dataclasses
import json

import pytest

from repro.core import (Budget, Planner, PlanRequest, PipetteStrategy,
                        SearchSpace, Workload, build_profile,
                        pipette_latency_ref, profile_bandwidth)
from repro.core.cluster import (A100_TIER, V100_TIER, MID_RANGE,
                                mixed_fleet_spec)
from repro.models.config import ModelConfig

pytest.importorskip("jax")

GPT = ModelConfig(name="g12", family="dense", n_layers=12, d_model=1024,
                  n_heads=16, n_kv_heads=16, d_ff=4096, vocab_size=32000)
MIXED = mixed_fleet_spec("det-mixed-16x1", 16, (A100_TIER, V100_TIER),
                         (0.5, 0.5), gpus_per_node=1, seed=31)


def _req(spec, backend, hierarchical=None, n_chains=2):
    return PlanRequest(
        workload=Workload(GPT, 2048, 32), spec=spec,
        space=SearchSpace(max_micro=2),
        budget=Budget(sa_seconds=60.0, sa_iters=40, n_chains=n_chains,
                      sa_topk=2, backend=backend,
                      hierarchical=hierarchical),
        seed=11)


def _plan_json(spec, backend, **kw):
    bw, _ = profile_bandwidth(spec)
    return Planner(PipetteStrategy()).plan(_req(spec, backend, **kw),
                                           bw).to_json()


def _strip_backend(text):
    d = json.loads(text)
    assert d["provenance"]["budget"].pop("backend") in ("numpy", "jax")
    return json.dumps(d, sort_keys=True)


@pytest.mark.parametrize("spec", [MID_RANGE, MIXED],
                         ids=["uniform", "mixed"])
def test_numpy_and_jax_plans_byte_identical(spec):
    """Same request + seed, both executors: identical plans except the
    recorded backend name itself."""
    a = _plan_json(spec, "numpy")
    b = _plan_json(spec, "jax")
    assert a != b                       # the backend field does differ...
    assert _strip_backend(a) == _strip_backend(b)   # ...and nothing else


def test_backends_agree_under_hierarchical_search():
    a = _plan_json(MIXED, "numpy", hierarchical=True)
    b = _plan_json(MIXED, "jax", hierarchical=True)
    assert _strip_backend(a) == _strip_backend(b)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_same_backend_rerun_is_byte_identical(backend):
    assert _plan_json(MIXED, backend) == _plan_json(MIXED, backend)


def test_multi_chain_plans_agree_chain_for_chain():
    """n_chains > 1 exercises the per-chain RNG streams and the winner
    argmin on both executors."""
    a = _plan_json(MIXED, "numpy", n_chains=3)
    b = _plan_json(MIXED, "jax", n_chains=3)
    assert _strip_backend(a) == _strip_backend(b)


def test_warm_planner_traces_nothing_and_keeps_byte_parity():
    """A second plan of a request that differs only in its seed reuses
    the first plan's executables (two shape groups, each ``score`` and
    ``anneal``), and is still byte-identical to the NumPy backend's."""
    import jax
    from repro import obs
    bw, _ = profile_bandwidth(MIXED)
    planner = Planner(PipetteStrategy())
    planner.plan(_req(MIXED, "jax"), bw)
    events = collections.Counter()

    def on_event(e, **kw):
        if e.startswith((obs.TRACE_EVENT, obs.EXE_HIT_EVENT)):
            events[e] += 1

    req = dataclasses.replace(_req(MIXED, "jax"), seed=12)
    jax.monitoring.register_event_listener(on_event)
    try:
        warm = planner.plan(req, bw).to_json()
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    assert events == {obs.EXE_HIT_EVENT + "jax_engine.score": 2,
                      obs.EXE_HIT_EVENT + "jax_engine.anneal": 2}
    want = Planner(PipetteStrategy()).plan(
        dataclasses.replace(_req(MIXED, "numpy"), seed=12), bw).to_json()
    assert _strip_backend(warm) == _strip_backend(want)


def test_pallas_interpret_matches_ref_kernels(monkeypatch):
    """The jax backend with its group reduces in the (interpreted) float32
    kernels, as on TPU, picks the configuration the float64 reference path
    picks, and its latency is the float64 re-score of its own mapping
    within the TPU tolerance."""
    from repro.core import jax_engine
    from repro.core.jax_engine import TPU_REL_TOL
    bw, _ = profile_bandwidth(MIXED)
    req = _req(MIXED, "jax")
    ref = Planner(PipetteStrategy()).plan(req, bw)
    monkeypatch.setattr(jax_engine, "kernels_mode", lambda k: "interpret")
    pal = Planner(PipetteStrategy()).plan(req, bw)
    assert pal.conf == ref.conf
    assert pal.latency == pytest.approx(ref.latency, rel=TPU_REL_TOL)
    prof = build_profile(req.workload, MIXED, pal.conf)
    assert pal.latency == pytest.approx(
        pipette_latency_ref(pal.conf, pal.mapping, bw, prof, MIXED),
        rel=TPU_REL_TOL)


def test_legacy_default_backend_differs_only_in_budget_fields():
    """A Budget that names no backend runs the NumPy executor: its plan
    is byte-identical to the explicit ``backend="numpy"`` plan, records
    ``"numpy"``, and leaves ``hierarchical`` on auto (null)."""
    bw, _ = profile_bandwidth(MIXED)
    req = _req(MIXED, "numpy")
    default = dataclasses.replace(req, budget=Budget(
        sa_seconds=60.0, sa_iters=40, n_chains=2, sa_topk=2))
    plan = Planner(PipetteStrategy()).plan(default, bw)
    assert plan.to_json() == Planner(PipetteStrategy()).plan(
        req, bw).to_json()
    d = plan.to_json_dict()
    assert d["provenance"]["budget"]["backend"] == "numpy"
    assert d["provenance"]["budget"]["hierarchical"] is None
    assert plan.feasible
