"""Spans and trace counters of the planner (``repro.obs``): what each emits
to ``jax.monitoring``, how often, and that ``Overhead`` reads the spans."""
import collections

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (Budget, Planner, PlanRequest, PipetteStrategy,
                        SearchSpace, Workload, build_profile,
                        profile_bandwidth)
from repro.core.cluster import A100_TIER, V100_TIER, mixed_fleet_spec
from repro.core.jax_engine import JaxDedicationEngine, clear_executables
from repro.core.memory import enumerate_confs
from repro.models.config import ModelConfig

GPT = ModelConfig(name="g12", family="dense", n_layers=12, d_model=1024,
                  n_heads=16, n_kv_heads=16, d_ff=4096, vocab_size=32000)
MIXED = mixed_fleet_spec("obs-mixed-16x1", 16, (A100_TIER, V100_TIER),
                         (0.5, 0.5), gpus_per_node=1, seed=31)
SEARCH = ("search.enumerate", "search.mem_estimate", "search.profile",
          "search.prescore")
GROUP = ("sa.engine", "sa.coarse", "sa.anneal")


@pytest.fixture
def events():
    """``(event, seconds, attributes)`` of every ``jax.monitoring`` event
    recorded while the test runs; counts have seconds 0."""
    got = []

    def on_duration(e, d, **kw):
        got.append((e, d, kw))

    def on_event(e, **kw):
        got.append((e, 0.0, kw))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    yield got
    jax.monitoring.unregister_event_duration_listener(on_duration)
    jax.monitoring.unregister_event_listener(on_event)


@pytest.fixture(autouse=True)
def _no_kept_executables():
    """Each test starts as a fresh process would: nothing compiled."""
    clear_executables()


def _ours(events, prefix="/pipette/"):
    return [(e[len(prefix):], d, kw) for e, d, kw in events
            if e.startswith(prefix)]


def _plan(backend, sa_iters=40):
    req = PlanRequest(
        workload=Workload(GPT, 2048, 32), spec=MIXED,
        space=SearchSpace(max_micro=2),
        budget=Budget(sa_seconds=60.0, sa_iters=sa_iters, n_chains=2,
                      sa_topk=2, backend=backend),
        seed=11)
    bw, _ = profile_bandwidth(MIXED)
    return Planner(PipetteStrategy()).plan(req, bw)


def test_span_emits_one_duration_event_with_request(events):
    with obs.request() as rid:
        with obs.span("test.body", stage="x") as s:
            sum(range(1000))
    got = _ours(events, obs.SPAN_EVENT)
    assert got == [("test.body", s.seconds, {"request": rid})]
    assert s.seconds > 0 and rid > 0


def test_requests_get_fresh_ids_and_restore_the_outer_one(events):
    with obs.request() as outer:
        with obs.request() as inner:
            with obs.span("test.inner"):
                pass
        with obs.span("test.outer"):
            pass
    with obs.span("test.none"):
        pass
    ids = [kw["request"] for _, _, kw in _ours(events, obs.SPAN_EVENT)]
    assert ids == [inner, outer, 0] and inner != outer


def test_trace_counter_emits_one_event_with_request(events):
    with obs.request() as rid:
        obs.count_trace("test.f")
    obs.count_trace("test.f")
    assert _ours(events) == [("trace/test.f", 0.0, {"request": rid}),
                             ("trace/test.f", 0.0, {"request": 0})]


def test_engine_counts_its_traces_not_its_calls(events):
    bw, _ = profile_bandwidth(MIXED)
    conf = next(c for c in enumerate_confs(MIXED.n_gpus, 32,
                                           n_layers=GPT.n_layers)
                if c.pp > 1 and c.bs_micro <= 2)
    prof = build_profile(Workload(GPT, 2048, 32), MIXED, conf)
    rng = np.random.default_rng(0)

    def traces(prefix=obs.TRACE_EVENT):
        return collections.Counter(n for n, _, _ in _ours(events, prefix))

    jeng = JaxDedicationEngine([conf], [prof], bw, MIXED)
    jeng.score(rng.permutation(MIXED.n_gpus))
    jeng.score(rng.permutation(MIXED.n_gpus))
    jeng.score_batch(np.stack([rng.permutation(MIXED.n_gpus)] * 2))
    jeng.score_batch(np.stack([rng.permutation(MIXED.n_gpus)] * 2))
    assert traces() == {"jax_engine.score": 1, "jax_engine.score_batch": 1}
    # a second engine of the same shape reuses the executable
    JaxDedicationEngine([conf], [prof], bw, MIXED).score(
        rng.permutation(MIXED.n_gpus))
    assert traces() == {"jax_engine.score": 1, "jax_engine.score_batch": 1}
    assert traces(obs.EXE_HIT_EVENT) == {"jax_engine.score": 1}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_plan_spans_once_per_stage_and_per_group(events, backend):
    plan = _plan(backend)
    spans = collections.Counter(n for n, _, _ in
                                _ours(events, obs.SPAN_EVENT))
    n_groups = spans["sa.engine"]
    assert n_groups == 2                 # the top 2 are of two shapes
    assert spans == {**{n: 1 for n in SEARCH}, "sa.prepare": 1,
                     **{n: n_groups for n in GROUP}}
    assert n_groups == len({(c.conf.pp, c.conf.tp, c.conf.cp, c.conf.dp,
                             c.conf.vpp)
                            for c in plan.result.ranked if c.sa is not None})
    # one request id for every span and counter of the plan
    assert len({kw["request"] for _, _, kw in _ours(events)}) == 1
    traces = collections.Counter(n for n, _, _ in
                                 _ours(events, obs.TRACE_EVENT))
    assert traces == ({"jax_engine.score": n_groups,
                       "jax_engine.anneal": n_groups}
                      if backend == "jax" else {})


def test_overhead_reads_the_spans(events):
    ov = _plan("jax").result.overhead
    secs = {n: d for n, d, _ in _ours(events, obs.SPAN_EVENT)}
    assert (ov.enumerate_s, ov.mem_estimator_s, ov.profile_s,
            ov.prescore_s) == tuple(secs[n] for n in SEARCH)
    sa = sum(d for n, d, _ in _ours(events, obs.SPAN_EVENT)
             if n.startswith("sa."))
    assert 0.9 * ov.sa_s <= sa <= ov.sa_s


def test_plan_event_count_does_not_depend_on_sa_iters(events):
    counts = []
    for iters in (50, 200):
        clear_executables()
        events.clear()
        _plan("jax", sa_iters=iters)
        counts.append(collections.Counter(n for n, _, _ in _ours(events)))
    assert counts[0] == counts[1]
