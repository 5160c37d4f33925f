"""Compile rehearsals for a described TPU v5e (no chip needed).

XLA's TPU compiler compiles for a topology that is described, not
attached: these tests hand it the main path's device programs at real
shapes and check what only it can refuse — Mosaic layouts of the Pallas
group-reduce kernels, the float64 annealer, and whether the 12-layer
gpt-1.1b train step fits one chip's HBM.  The topology is described only
inside the fixtures below, so importing this module touches no TPU
library, and every test skips where no topology can be described.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

#: One TPU v5e chip's HBM.
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("m", [2, 4, 8])
def test_group_min_scale_compiles_for_v5e(one_chip, m):
    """1,280 groups of m: the TP groups of a 10,240-GPU fleet at tp=8, in
    the float64 the scorer hands over."""
    from repro.kernels.group_reduce import group_min_scale
    with jax.enable_x64(True):
        sub = jax.ShapeDtypeStruct((1280, m, m), jnp.float64,
                                   sharding=one_chip)
        exe = jax.jit(lambda s: group_min_scale(s, 25e9)).lower(
            sub).compile()
    assert "tpu_custom_call" in exe.as_text()


def test_group_max_compiles_for_v5e(one_chip):
    """Per-stage slowdowns of the 10,240-GPU fleet at pp=5: (5, 2,048)."""
    from repro.kernels.group_reduce import group_max
    with jax.enable_x64(True):
        vals = jax.ShapeDtypeStruct((5, 2048), jnp.float64,
                                    sharding=one_chip)
        exe = jax.jit(group_max).lower(vals).compile()
    assert "tpu_custom_call" in exe.as_text()


def test_annealer_compiles_for_v5e(one_chip):
    """The vmap-over-scan annealer at chip_smoke.py's 2,048-GPU shape: the
    configuration the planner picks there, 4 chains x 200 moves over the
    hierarchical islands, kernels compiled as on the chip."""
    from benchmarks.bench_configure import HUGE_BS_GLOBAL, M40, _huge_spec
    from repro.core import Conf, Workload, build_profile, profile_bandwidth
    from repro.core.annealing import build_islands, make_move_plan
    from repro.core.jax_engine import JaxDedicationEngine

    spec = _huge_spec(2048)
    bw, _ = profile_bandwidth(spec)
    conf = Conf(8, 8, 32, 1, HUGE_BS_GLOBAL)
    prof = build_profile(Workload(M40, 2048, HUGE_BS_GLOBAL), spec, conf)
    jeng = JaxDedicationEngine([conf], [prof], bw, spec, kernels="pallas")
    islands = build_islands(spec, hierarchical=True)
    mp = make_move_plan([len(i) for i in islands], 200, 4, 0)
    n = spec.n_gpus
    args = jeng.anneal_args(
        np.arange(n)[None], mp.oa[None], mp.ob[None], mp.kind, mp.thresh,
        mp.valid, mp.probe_oa[None], mp.probe_ob[None], mp.probe_kind)
    with jax.enable_x64(True):
        exe = jeng.compile_anneal(_on(one_chip, args))
    assert "tpu_custom_call" in exe.as_text()


def test_train_step_fits_one_v5e(one_chip):
    """chip_smoke.py's train step: gpt-1.1b at full width and 12 of 24
    layers, seq 2048, global batch 8 in 2 microbatches, AdamW state in
    float32 — its peak must fit one chip."""
    from repro import configs
    from repro.launch.steps import make_train_step
    from repro.models import model as M
    from repro.models.sharding import ShardCtx
    from repro.optim.adamw import AdamW, cosine_schedule

    cfg = configs.get("gpt-1.1b").replace(n_layers=12)
    opt = AdamW(lr=cosine_schedule(3e-4, 1, 5))
    params = _on(one_chip, jax.eval_shape(
        lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0)))
    opt_state = _on(one_chip, jax.eval_shape(opt.init, params))
    batch = {k: jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    exe = jax.jit(make_train_step(cfg, ShardCtx(), opt, n_micro=2),
                  donate_argnums=(0, 1)).lower(params, opt_state,
                                               batch).compile()
    assert exe.memory_analysis().peak_memory_in_bytes < V5E_HBM_BYTES


def test_pipelined_train_step_fits_four_v5e(topo):
    """The same model on a 2x2 v5e host at pp=4 with 8 microbatches, laid
    out by the train driver: each chip's peak must fit its HBM."""
    from jax.sharding import Mesh
    from repro import configs
    from repro.core import Conf
    from repro.launch.train import step_layout
    from repro.optim.adamw import AdamW, cosine_schedule

    cfg = configs.get("gpt-1.1b").replace(n_layers=12)
    opt = AdamW(lr=cosine_schedule(3e-4, 1, 3))
    conf = Conf(4, 1, 1, 1, 8)
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1, 1),
                ("pipe", "model", "data"))
    lay = step_layout(cfg, opt, n_micro=conf.n_mb, conf=conf, mesh=mesh)

    def on(shapes, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), shapes, shardings)

    params = on(jax.eval_shape(lay.init, jax.random.PRNGKey(0)), lay.params)
    opt_state = on(jax.eval_shape(opt.init, params), lay.opt_state)
    batch = {k: jax.ShapeDtypeStruct((8, 2048), jnp.int32,
                                     sharding=lay.batch)
             for k in ("tokens", "labels")}
    exe = lay.jit().lower(params, opt_state, batch).compile()
    assert "collective-permute" in exe.as_text()       # the stage hops
    assert exe.memory_analysis().peak_memory_in_bytes < V5E_HBM_BYTES
