"""The Planner API: configure() shim bit-exactness, Plan JSON round-trip,
byte-identical determinism per strategy, and Plan-driven mesh construction
(the acceptance criteria of the api_redesign issue)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import (MID_RANGE, AMPStrategy, Budget, Conf,
                        ExhaustiveStrategy, MegatronStrategy, Plan, Planner,
                        PlanRequest, PipetteStrategy, SearchSpace, Strategy,
                        VarunaStrategy, Workload, configure,
                        fit_memory_estimator, profile_bandwidth,
                        true_bandwidth_matrix)
from repro.configs.gpt_paper import GPT_3_1B
from repro.models.config import ModelConfig

SRC = str(Path(__file__).resolve().parent.parent / "src")

GPT = ModelConfig(name="g", family="dense", n_layers=16, d_model=1024,
                  n_heads=16, n_kv_heads=16, d_ff=4096, vocab_size=32000)
SPEC = MID_RANGE.with_nodes(1)                  # 8 GPUs: fast, full coverage
W = Workload(GPT, 2048, 32)

# iteration-bound SA budget: deterministic trajectories, tiny runtime
BUDGET = Budget(sa_seconds=60.0, sa_iters=80, sa_topk=4)
REQ = PlanRequest(workload=W, spec=SPEC,
                  space=SearchSpace(max_micro=4), budget=BUDGET, seed=7)


@pytest.fixture(scope="module")
def bw():
    return profile_bandwidth(SPEC)[0]


# ---------------------------------------------------------------------------
# configure() is a bit-exact shim over Planner(PipetteStrategy())
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("space_kw", [{}, {"max_cp": 2}],
                         ids=["3d", "4d_max_cp2"])
def test_configure_shim_bit_exact_midrange(space_kw):
    """Acceptance: on MID_RANGE (16 nodes / 128 GPUs), 3D and a max_cp=2 4D
    search, the legacy kwarg shim and the Planner entry point return the
    same best conf, the same mapping, the same latency — and the same full
    ranking."""
    spec = MID_RANGE
    w = Workload(GPT_3_1B, 2048, 256)
    bw_meas, _ = profile_bandwidth(spec)
    kw = dict(sa_seconds=60.0, sa_iters=60, sa_topk=4, max_micro=4, seed=3)
    res = configure(w, spec, bw_meas, **kw, **space_kw)
    req = PlanRequest(
        workload=w, spec=spec,
        space=SearchSpace(max_micro=4, **space_kw),
        budget=Budget(sa_seconds=60.0, sa_iters=60, sa_topk=4), seed=3)
    plan = Planner(PipetteStrategy()).plan(req, bw_meas)

    assert plan.conf == res.best.conf
    assert plan.latency == res.best.latency
    assert np.array_equal(plan.mapping, res.best.mapping)
    assert plan.mapping.dtype == res.best.mapping.dtype
    # full in-process ranking, not just the winner
    assert [c.conf for c in plan.result.ranked] == \
        [c.conf for c in res.ranked]
    assert [c.latency for c in plan.result.ranked] == \
        [c.latency for c in res.ranked]
    if space_kw.get("max_cp", 1) > 1:
        assert any(c.conf.cp > 1 for c in res.ranked)


def test_configure_dedicate_false_is_exhaustive_strategy(bw):
    res = configure(W, SPEC, bw, dedicate=False, max_micro=4, seed=7)
    plan = Planner(ExhaustiveStrategy()).plan(
        PlanRequest(workload=W, spec=SPEC, space=SearchSpace(max_micro=4),
                    seed=7), bw)
    assert plan.conf == res.best.conf
    assert plan.latency == res.best.latency
    assert np.array_equal(plan.mapping, res.best.mapping)


# ---------------------------------------------------------------------------
# all strategies behind the one interface
# ---------------------------------------------------------------------------

def _strategies(bw):
    return [PipetteStrategy(), ExhaustiveStrategy(), AMPStrategy(),
            VarunaStrategy(),
            MegatronStrategy(trials=3, bw_true=true_bandwidth_matrix(SPEC))]


def test_every_strategy_satisfies_protocol_and_plans(bw):
    for strat in _strategies(bw):
        assert isinstance(strat, Strategy)
        plan = Planner(strat).plan(REQ, bw)
        assert plan.provenance.strategy == strat.name
        assert plan.feasible
        assert plan.conf.n_gpus == SPEC.n_gpus
        assert sorted(np.asarray(plan.mapping).reshape(-1).tolist()) == \
            list(range(SPEC.n_gpus))
        assert plan.ranked[0].conf == plan.conf
        assert [c.latency for c in plan.ranked] == \
            sorted(c.latency for c in plan.ranked)
        # baselines stay 3D by design
        if strat.name in ("amp", "varuna", "megatron-lm"):
            assert all(c.conf.cp == 1 for c in plan.ranked)


def test_strategy_names_are_distinct(bw):
    names = [s.name for s in _strategies(bw)]
    assert len(set(names)) == len(names)


# ---------------------------------------------------------------------------
# Plan JSON round-trip
# ---------------------------------------------------------------------------

def test_plan_roundtrip_preserves_everything(tmp_path, bw):
    plan = Planner(PipetteStrategy()).plan(REQ, bw)
    p = tmp_path / "plan.json"
    plan.save(p)
    back = Plan.load(p)

    assert back.conf == plan.conf
    assert back.latency == plan.latency
    assert np.array_equal(back.mapping, plan.mapping)
    assert back.mapping.dtype == plan.mapping.dtype      # dtype preserved
    assert back.mapping.shape == plan.mapping.shape      # shape preserved
    assert len(back.ranked) == len(plan.ranked)
    for a, b in zip(back.ranked, plan.ranked):
        assert a.conf == b.conf and a.latency == b.latency
        assert np.array_equal(a.mapping, b.mapping)
        assert a.mapping.dtype == b.mapping.dtype
        # NaN mem_pred (no estimator) must survive the null round trip
        assert (a.mem_pred == b.mem_pred or
                (np.isnan(a.mem_pred) and np.isnan(b.mem_pred)))
    pv, qv = back.provenance, plan.provenance
    assert (pv.strategy, pv.seed, pv.bw_digest) == \
        (qv.strategy, qv.seed, qv.bw_digest)
    assert pv.space == qv.space and pv.budget == qv.budget
    assert back.overhead.n_candidates == plan.overhead.n_candidates
    assert back.overhead.n_enumerated == plan.overhead.n_enumerated
    # the in-process search result is deliberately not serialized
    assert plan.result is not None and back.result is None
    # re-serializing the loaded plan is byte-identical (fixed point)
    assert back.to_json() == plan.to_json()


def test_plan_roundtrip_4d_mapping(tmp_path, bw):
    """cp>1 mappings are 4D (pp, tp, cp, dp); the JSON round trip must
    bring the rank-4 shape back exactly."""
    req = PlanRequest(workload=W, spec=SPEC,
                      space=SearchSpace(max_micro=4, max_cp=2),
                      budget=BUDGET, seed=7)
    plan = Planner(PipetteStrategy()).plan(req, bw, keep_top=50)
    four_d = [c for c in plan.ranked if c.conf.cp > 1]
    assert four_d, "4D search produced no cp>1 candidates in the top-k"
    p = tmp_path / "plan4d.json"
    plan.save(p)
    back = Plan.load(p)
    for a, b in zip(back.ranked, plan.ranked):
        assert a.mapping.shape == b.mapping.shape
        assert np.array_equal(a.mapping, b.mapping)
    four_d_back = [c for c in back.ranked if c.conf.cp > 1]
    assert four_d_back[0].mapping.ndim == 4
    assert four_d_back[0].mapping.shape == four_d[0].mapping.shape


def test_plan_estimator_provenance(tmp_path, bw):
    est = fit_memory_estimator([W], SPEC, fit_nodes=1, steps=300,
                               residual=True)
    plan = Planner(PipetteStrategy(estimator=est)).plan(REQ, bw)
    e = plan.provenance.estimator
    assert e is not None
    assert e["residual"] is True and e["with_cp"] is False
    assert e["fit_gpu_mem"] == SPEC.gpu_mem
    assert e["fit_gpus_per_node"] == SPEC.gpus_per_node
    p = tmp_path / "plan.json"
    plan.save(p)
    assert Plan.load(p).provenance.estimator == e
    # memory predictions came through the estimator, not NaN
    assert np.isfinite(plan.mem_pred)


def test_infeasible_plan_roundtrip_and_mesh_refusal(tmp_path, bw):
    """Every candidate pruned -> a feasible=False Plan that still
    serializes (recording the outcome) and that the launch layer refuses
    to build a mesh from."""
    est = fit_memory_estimator([W], SPEC, fit_nodes=1, steps=300,
                               residual=True)
    plan = Planner(PipetteStrategy(estimator=est, mem_limit=1.0)).plan(
        REQ, bw)
    assert not plan.feasible
    assert plan.conf is None and plan.mapping is None
    assert plan.latency == float("inf")
    p = tmp_path / "infeasible.json"
    plan.save(p)
    back = Plan.load(p)
    assert not back.feasible and back.ranked == ()
    from repro.launch.mesh import mesh_from_plan
    with pytest.raises(ValueError, match="infeasible"):
        mesh_from_plan(back)


def test_plan_rejects_unknown_schema_version(tmp_path, bw):
    plan = Planner(AMPStrategy()).plan(REQ, bw)
    d = plan.to_json_dict()
    d["version"] = 99
    p = tmp_path / "future.json"
    p.write_text(json.dumps(d))
    with pytest.raises(ValueError, match="schema version"):
        Plan.load(p)


# ---------------------------------------------------------------------------
# determinism: same request + seed -> byte-identical JSON, every strategy
# ---------------------------------------------------------------------------

def test_plan_json_byte_identical_across_runs(tmp_path, bw):
    for strat in _strategies(bw):
        a = Planner(strat).plan(REQ, bw).save(tmp_path / "a.json")
        b = Planner(strat).plan(REQ, bw).save(tmp_path / "b.json")
        assert Path(a).read_bytes() == Path(b).read_bytes(), strat.name


def test_bw_digest_tracks_the_matrix(bw):
    plan_a = Planner(AMPStrategy()).plan(REQ, bw)
    plan_b = Planner(AMPStrategy()).plan(REQ, bw + 1.0)
    assert plan_a.provenance.bw_digest != plan_b.provenance.bw_digest


def test_megatron_digest_fingerprints_the_scoring_matrix(bw):
    """MegatronStrategy(bw_true=...) runs its trials on bw_true, ignoring
    the profiled bw — provenance must fingerprint the matrix the latencies
    actually came from, else the staleness check validates noise."""
    from repro.core import bw_fingerprint
    bw_true = true_bandwidth_matrix(SPEC)
    plan = Planner(MegatronStrategy(trials=3, bw_true=bw_true)).plan(REQ, bw)
    assert plan.provenance.bw_digest == bw_fingerprint(bw_true)
    assert plan.provenance.bw_digest != bw_fingerprint(bw)
    # without a bw_true override the handed-in matrix is the scoring one
    plan2 = Planner(MegatronStrategy(trials=3)).plan(REQ, bw)
    assert plan2.provenance.bw_digest == bw_fingerprint(bw)


# ---------------------------------------------------------------------------
# a saved Plan drives mesh construction without re-running the search
# ---------------------------------------------------------------------------

def test_cli_plan_reloads_and_drives_mesh(tmp_path):
    """Acceptance: `python -m repro.plan plan` writes the artifact; a fresh
    process (8 forced host devices, no search) loads it and builds the
    Mesh straight from the mapping."""
    out = tmp_path / "plan.json"
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.plan", "plan",
         "--config", "qwen2-7b", "--reduced", "--cluster", "mid-range",
         "--nodes", "1", "--seq", "128", "--bs-global", "16",
         "--sa-iters", "100", "-o", str(out)],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert out.exists()

    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    code = f"""
        import numpy as np
        from repro.core import Plan
        from repro.launch.mesh import mesh_from_plan
        plan = Plan.load({str(out)!r})
        mesh = mesh_from_plan(plan)
        assert mesh.devices.shape == plan.mapping.shape
        assert mesh.axis_names[:2] == ("pipe", "model")
        want = np.asarray(plan.mapping).reshape(-1).tolist()
        got = [d.id for d in mesh.devices.reshape(-1)]
        assert got == want, (got, want)
        print("MESH_OK", mesh.devices.shape)
    """
    r2 = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                        capture_output=True, text=True, timeout=600, env=env)
    assert r2.returncode == 0, f"stdout:\n{r2.stdout}\nstderr:\n{r2.stderr}"
    assert "MESH_OK" in r2.stdout


# ---------------------------------------------------------------------------
# runtime consumption: TrainLoop persists plan provenance; replan emits one
# ---------------------------------------------------------------------------

def test_trainloop_persists_plan_json(tmp_path, bw):
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import DataLoader, LoaderConfig, SyntheticCorpus
    from repro.optim.adamw import AdamW
    from repro.runtime.trainer import TrainLoop, TrainLoopConfig

    plan = Planner(PipetteStrategy()).plan(REQ, bw)
    opt = AdamW(lr=0.05, weight_decay=0.0)

    @jax.jit
    def step(params, opt_state, batch):
        x = jnp.asarray(batch["tokens"], jnp.float32) / 10.0
        y = jnp.asarray(batch["labels"], jnp.float32) / 10.0
        loss, g = jax.value_and_grad(
            lambda p: jnp.mean((x @ p["w"] - y) ** 2))(params)
        params, opt_state = opt.update(g, opt_state, params)
        return params, opt_state, {"loss": loss}

    loader = DataLoader(SyntheticCorpus(vocab_size=9, seed=1),
                        LoaderConfig(4, 8))
    cfg = TrainLoopConfig(total_steps=3, ckpt_every=3,
                          ckpt_dir=str(tmp_path / "run"))
    params = {"w": jnp.zeros((8, 8))}
    loop = TrainLoop(cfg, step, loader, plan=plan)
    loop.run(params, opt.init(params), resume=False)

    saved = Plan.load(loop.plan_path())
    assert saved.conf == plan.conf
    assert np.array_equal(saved.mapping, plan.mapping)
    assert saved.provenance.bw_digest == plan.provenance.bw_digest


def test_replan_returns_plan_artifact(tmp_path):
    from repro.runtime.elastic import replan
    ep = replan(W, SPEC.with_nodes(4), healthy_nodes=3, sa_seconds=0.1,
                sa_topk=2)
    assert ep.plan is not None and ep.plan.feasible
    assert ep.plan.conf.n_gpus == 24
    assert ep.plan.provenance.strategy == "pipette"
    assert ep.result is ep.plan.result      # full ranking still exposed
    p = tmp_path / "replan.json"
    ep.plan.save(p)
    assert Plan.load(p).conf == ep.plan.conf


def test_replan_rejects_unknown_kwargs():
    from repro.runtime.elastic import replan
    with pytest.raises(TypeError, match="unknown replan"):
        replan(W, SPEC, healthy_nodes=1, not_a_knob=3)


# ---------------------------------------------------------------------------
# request validation
# ---------------------------------------------------------------------------

def test_request_dataclasses_validate_and_freeze():
    with pytest.raises(ValueError):
        SearchSpace(max_cp=0)
    with pytest.raises(ValueError):
        Budget(sa_iters=0)
    req = PlanRequest(workload=W, spec=SPEC)
    with pytest.raises(Exception):          # frozen
        req.seed = 1
    assert req.space == SearchSpace() and req.budget == Budget()


def test_budget_backend_is_numpy_or_jax():
    """One SA driver with two executors: the NumPy one is the default,
    and no other value (``None`` included) names an executor."""
    assert Budget().backend == "numpy"
    assert Budget(backend="jax").backend == "jax"
    for bad in (None, "legacy", ""):
        with pytest.raises(ValueError, match="backend"):
            Budget(backend=bad)


def test_conf_roundtrip_via_plan_schema():
    from repro.core.plan import _conf_in, _conf_out
    for conf in (Conf(2, 2, 2, 2, 64), Conf(2, 2, 1, 2, 32, cp=2),
                 Conf(1, 8, 1, 4, 32)):
        assert _conf_in(_conf_out(conf)) == conf
