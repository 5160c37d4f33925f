"""The planned training path on several devices: ``step_layout`` for a
plan's layouts against the plain reference, ``plan_for_devices`` from a
recorded link matrix, and the trace counter of ``StepLayout.compile``.

The multi-device cases run in subprocesses with four forced host devices,
as ``test_multidevice.py`` does, because JAX fixes the device count at its
first use."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def run_py(code: str, devices: int = 4, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                        f"device_count={devices}").strip()
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


#: (pp, tp, dp, microbatches) of the four-device layouts
LAYOUTS = {"pp4_mb4": (4, 1, 1, 4), "pp2_tp2_mb2": (2, 2, 1, 2),
           "tp4": (1, 4, 1, 1), "tp2_dp2": (1, 2, 2, 1)}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_step_layout_matches_reference(layout):
    """A Qwen2-shaped model (grouped-query attention with 2 key-value
    heads, so tp4 keeps them replicated; q/k/v biases moved off zero; tied
    embedding) through ``step_layout``: the loss and every leaf of the
    first gradient, read off Adam's first moment, against
    ``dense_ref.loss_and_grad`` at HIGHEST.  The program rounds the head's
    input to bfloat16 (8 bits): the loss moves by a few parts in 1e4,
    each leaf's gradient by under 1%."""
    pp, tp, dp, n_mb = LAYOUTS[layout]
    out = run_py(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import Conf
        from repro.launch.pipeline import model_stage_params
        from repro.launch.train import step_layout
        from repro.models.config import ModelConfig
        from repro.optim.adamw import AdamW
        from bench.reference import dense_ref
        from bench.traffic.tokens import batch

        pp, tp, dp, n_mb = {pp}, {tp}, {dp}, {n_mb}
        m = dict(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                 head_dim=16, d_ff=128, vocab_size=300, qkv_bias=True,
                 tie_embeddings=True, rope_theta=1e6, norm_eps=1e-6)
        cfg = ModelConfig(name="q", family="dense", dtype="float32", **m)
        b1 = 0.9
        opt = AdamW(lr=1e-3, b1=b1, b2=0.95, eps=1e-8, weight_decay=0.1,
                    grad_clip=1e9)
        mesh = Mesh(np.asarray(jax.devices()).reshape(pp, tp, dp),
                    ("pipe", "model", "data"))
        conf = Conf(pp, tp, dp, 4 // (dp * n_mb), 4)
        assert conf.n_mb == n_mb
        lay = step_layout(cfg, opt, n_micro=n_mb, conf=conf, mesh=mesh)
        params = dense_ref.init(m, dense_ref.key(3))
        params["layers"] = dict(params["layers"], **{{
            k: params["layers"][k] + 0.1 for k in ("bq", "bk", "bv")}})
        b = batch(3, 1, 4, 32, m["vocab_size"])
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_g = dense_ref.loss_and_grad(m, params, b, 4)
            prog = model_stage_params(params, pp) if pp > 1 else params
            prog = jax.device_put(prog, lay.params)
            state = jax.jit(opt.init, out_shardings=lay.opt_state)(prog)
            exe = lay.compile(prog, state, lay.put_batch(b))
            _, state, met = exe(prog, state, lay.put_batch(b))
        loss = float(met["loss"])
        assert abs(loss - ref_loss) / ref_loss < 1e-3, (loss, ref_loss)
        g = dense_ref._named(jax.tree.map(lambda x: x / (1 - b1), state.m))
        r = dense_ref._named(ref_g)
        assert sorted(g) == sorted(r)
        for k in r:
            gk = np.asarray(g[k]).reshape(-1)
            rk = np.asarray(r[k]).reshape(-1)
            gap = np.linalg.norm(gk - rk) / np.linalg.norm(rk)
            assert gap <= 1e-2, (k, gap)
        print("OK", {layout!r})
    """)
    assert "OK" in out


def test_plan_for_devices_from_recorded_matrix_does_not_profile():
    """With ``bw`` given nothing is profiled, one matrix and seed give one
    plan twice, and each plan is one ``train.plan`` span."""
    out = run_py("""
        import numpy as np, jax
        import repro.core.cluster as cluster
        from repro import configs
        from repro.launch.train import plan_for_devices

        def no_profile(*a, **k):
            raise AssertionError("profiled a recorded matrix")
        cluster.profile_bandwidth_live = no_profile

        spans = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, d, **kw: spans.append(e))
        cfg = configs.get("qwen2-7b").reduced()
        bw = np.full((4, 4), 2e9)
        np.fill_diagonal(bw, np.inf)
        runs = [plan_for_devices(cfg, 128, 8, seed=2**31 + 11, bw=bw)
                for _ in range(2)]
        (a, spec, got), (b, _, _) = runs
        assert np.array_equal(got, bw) and spec.n_gpus == 4
        assert a.conf == b.conf and a.latency == b.latency
        assert np.array_equal(a.mapping, b.mapping)
        assert spans.count("/pipette/span/train.plan") == 2
        print("OK", a.conf)
    """)
    assert "OK" in out


def test_step_layout_compile_fires_trace_counter_once():
    import jax
    from repro import obs
    from repro.launch.train import step_layout
    from repro.models.config import ModelConfig
    from repro.optim.adamw import AdamW

    cfg = ModelConfig(name="q", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=256,
                      head_dim=8, qkv_bias=True, tie_embeddings=True,
                      dtype="float32")
    opt = AdamW(lr=1e-3)
    lay = step_layout(cfg, opt, n_micro=1)
    params = lay.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    tokens = jax.numpy.zeros((2, 16), jax.numpy.int32)
    seen = []

    def on_event(event, **kw):
        if event.startswith(obs.TRACE_EVENT):
            seen.append(event)

    jax.monitoring.register_event_listener(on_event)
    try:
        lay.compile(params, state,
                    lay.put_batch({"tokens": tokens, "labels": tokens}))
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    assert seen == [obs.TRACE_EVENT + "launch.train_step"]


@pytest.mark.parametrize("pp,n_mb", [(4, 4), (4, 6), (2, 1)])
def test_pipelined_step_lowering_fires_pipeline_head_once(pp, n_mb):
    """Lowering a pipelined step fires ``launch.pipeline_head`` once, with
    ``ceil(n_mb / pp)`` head slots a stage; running the compiled step
    fires nothing."""
    out = run_py(f"""
        import math
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro import obs
        from repro.core import Conf
        from repro.launch.train import step_layout
        from repro.models.config import ModelConfig
        from repro.optim.adamw import AdamW

        pp, n_mb = {pp}, {n_mb}
        cfg = ModelConfig(name="q", family="dense", n_layers=4, d_model=32,
                          n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=256,
                          head_dim=8, qkv_bias=True, tie_embeddings=True,
                          dtype="float32")
        opt = AdamW(lr=1e-3)
        mesh = Mesh(np.asarray(jax.devices()[:pp]).reshape(pp, 1, 1),
                    ("pipe", "model", "data"))
        lay = step_layout(cfg, opt, n_micro=n_mb, conf=Conf(pp, 1, 1, 1, n_mb),
                          mesh=mesh)
        params = jax.device_put(lay.init(jax.random.PRNGKey(0)), lay.params)
        state = jax.jit(opt.init, out_shardings=lay.opt_state)(params)
        tokens = np.zeros((n_mb, 16), np.int32)
        seen = []

        def on_event(event, **kw):
            if event.startswith(obs.LAYOUT_EVENT):
                seen.append((event, kw["slots"], kw["n_mb"]))

        jax.monitoring.register_event_listener(on_event)
        b = lay.put_batch({{"tokens": tokens, "labels": tokens}})
        exe = lay.compile(params, state, b)
        for _ in range(2):
            params, state, _ = exe(params, state,
                                   lay.put_batch({{"tokens": tokens,
                                                  "labels": tokens}}))
        jax.block_until_ready(params)
        assert seen == [(obs.LAYOUT_EVENT + "launch.pipeline_head",
                         math.ceil(n_mb / pp), n_mb)], seen
        print("OK", seen)
    """)
    assert "OK" in out
