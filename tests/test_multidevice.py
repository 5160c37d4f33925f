"""Multi-device behaviour (shard_map MoE, GSPMD equivalence, pipeline
parallelism) — run in subprocesses with forced host device counts because
jax fixes the device count at first init."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_py(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_moe_shard_map_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.config import ModelConfig
        from repro.models import model as M
        from repro.models.sharding import ShardCtx, tree_shardings

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model", fsdp=("data",))
        cfg = ModelConfig(name="moe", family="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=256,
                          head_dim=16, n_experts=8, experts_per_token=2,
                          capacity_factor=8.0, dtype="float32", remat=False)
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        toks = jax.random.randint(key, (4, 32), 0, 256)
        batch = {"tokens": toks, "labels": toks}
        loss_ref, _ = M.loss_fn(params, cfg, ShardCtx(), batch)
        ps = jax.device_put(params, tree_shardings(params, cfg, ctx))
        bs = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
        with jax.set_mesh(mesh):
            loss_sh = jax.jit(lambda p, b: M.loss_fn(p, cfg, ctx, b)[0])(ps, bs)
        diff = abs(float(loss_ref) - float(loss_sh))
        assert diff < 1e-5, diff
        print("OK", diff)
    """)
    assert "OK" in out


def test_uneven_head_seq_sharding_matches():
    """granite-style head count (not divisible by model axis): the
    seq-sharded attention path must agree with single-device math."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.config import ModelConfig
        from repro.models import model as M
        from repro.models.sharding import ShardCtx, tree_shardings

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model", fsdp=())
        cfg = ModelConfig(name="d", family="dense", n_layers=2, d_model=60,
                          n_heads=3, n_kv_heads=3, d_ff=128, vocab_size=256,
                          head_dim=20, dtype="float32", remat=False)
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        toks = jax.random.randint(key, (4, 32), 0, 256)
        batch = {"tokens": toks, "labels": toks}
        loss_ref, _ = M.loss_fn(params, cfg, ShardCtx(), batch)
        ps = jax.device_put(params, tree_shardings(params, cfg, ctx))
        bs = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
        with jax.set_mesh(mesh):
            loss_sh = jax.jit(lambda p, b: M.loss_fn(p, cfg, ctx, b)[0])(ps, bs)
        diff = abs(float(loss_ref) - float(loss_sh))
        assert diff < 1e-5, diff
        print("OK", diff)
    """)
    assert "OK" in out


def test_pipeline_parallel_loss_and_grads_match():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.pipeline import pipeline_loss_fn, stage_params_split

        pp, L, d, V, mb, n_mb, S = 4, 8, 32, 64, 2, 8, 16
        mesh = jax.make_mesh((pp,), ("pipe",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 4)
        layers = {"w": jax.random.normal(ks[0], (L, d, d)) * 0.05}
        shared = {"embed": jax.random.normal(ks[1], (V, d)) * 0.1,
                  "head": jax.random.normal(ks[2], (d, V)) * 0.1}
        embed_fn = lambda sh, t: sh["embed"][t]
        def stage_fn(st, x):
            h, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, st["w"])
            return h
        def head_loss_fn(sh, h, lbl):
            lg = h @ sh["head"]
            lse = jax.nn.logsumexp(lg, -1)
            pick = jnp.take_along_axis(lg, lbl[..., None], -1)[..., 0]
            return jnp.mean(lse - pick)
        toks = jax.random.randint(ks[3], (n_mb, mb, S), 0, V)
        lbls = jax.random.randint(ks[3], (n_mb, mb, S), 0, V)
        def ref_loss(layers):
            tot = 0.0
            for i in range(n_mb):
                h = embed_fn(shared, toks[i])
                h, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), h,
                                    layers["w"])
                tot += head_loss_fn(shared, h, lbls[i])
            return tot / n_mb
        params = {"stages": stage_params_split(layers, pp), "shared": shared}
        loss_fn = pipeline_loss_fn(embed_fn, stage_fn, head_loss_fn, mesh)
        with jax.set_mesh(mesh):
            lp = jax.jit(loss_fn)(params, toks, lbls)
            gp = jax.jit(jax.grad(loss_fn))(params, toks, lbls)
        lr = ref_loss(layers)
        assert abs(float(lp - lr)) < 1e-5
        gr = jax.grad(ref_loss)(layers)
        np.testing.assert_allclose(
            np.asarray(gp["stages"]["w"]).reshape(L, d, d),
            np.asarray(gr["w"]), rtol=3e-4, atol=3e-5)
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_pipette_mapping_builds_mesh():
    """The SA mapping feeds jax Mesh construction (device assignment)."""
    out = run_py("""
        import numpy as np, jax
        from repro.core import Conf
        from repro.launch.mesh import mesh_from_mapping
        conf = Conf(2, 2, 2, 1, 16)
        rng = np.random.default_rng(0)
        mapping = rng.permutation(8).reshape(2, 2, 2)
        mesh = mesh_from_mapping(conf, mapping)
        ids = np.vectorize(lambda d: d.id)(mesh.devices)
        assert (ids == mapping).all()
        assert mesh.axis_names == ("pipe", "model", "data")
        print("OK")
    """)
    assert "OK" in out
