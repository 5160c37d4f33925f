"""The plain references against the program at a tiny size on the CPU."""
import numpy as np
import jax
import jax.numpy as jnp

from bench.reference import dense_ref, plan_ref
from bench.tests.helpers import small_plan, small_train
from bench.traffic.tokens import batch


def test_dense_reference_matches_program_loss_and_grads():
    from repro.models import model as M
    from repro.models.config import ModelConfig
    from repro.models.sharding import ShardCtx

    _, _, config, traffic = small_train()
    m = dense_ref.arch(config)
    cfg = ModelConfig(name="t", family="dense", n_layers=m["n_layers"],
                      d_model=m["d_model"], n_heads=m["n_heads"],
                      n_kv_heads=m["n_kv_heads"], d_ff=m["d_ff"],
                      vocab_size=m["vocab_size"], head_dim=m["head_dim"],
                      qkv_bias=m["qkv_bias"], rope_theta=m["rope_theta"],
                      norm_eps=m["norm_eps"],
                      tie_embeddings=m["tie_embeddings"], dtype="float32")
    params = dense_ref.init(m, dense_ref.key(3))
    # biases start at zero; move them so that the comparison covers them
    params["layers"] = dict(params["layers"], **{
        b: params["layers"][b] + 0.1 for b in ("bq", "bk", "bv")})
    b = batch(3, 1, 4, 32, m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, ShardCtx(),
                                {k: jnp.asarray(v) for k, v in b.items()}),
            has_aux=True)(params)
        ref_loss, ref_grads = dense_ref.loss_and_grad(m, params, b, 2)
    # the program rounds the head's input to bfloat16 (8 bits): the loss
    # moves by a few parts in 1e4, each leaf's gradient by under 1%
    assert abs(float(loss) - ref_loss) / ref_loss < 1e-3
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.linalg.norm(g - r)) <= 1e-2 * float(
            jnp.linalg.norm(r))


def test_plan_reference_matches_program_oracle():
    from repro.core import build_profile, pipette_latency_ref
    from bench.drivers.plan import Cell
    from bench.run import Context

    _, cell, config, traffic = small_plan()
    drv = Cell(Context(cell, config, dict(traffic, backend="numpy"), 5,
                       jax.devices()))
    drv._inputs()
    p = drv.plan(1)
    conf = drv._conf(p.conf)
    ref = plan_ref.latency(conf, p.mapping, drv.bw, config["model"],
                           config["job"], config["fleet"], drv.slow)
    prog = pipette_latency_ref(p.conf, p.mapping, drv.bw,
                               build_profile(drv.workload, drv.spec, p.conf),
                               drv.spec)
    assert abs(ref - prog) <= 1e-12 * prog
    assert abs(ref - p.latency) <= 1e-12 * prog
    assert plan_ref.mapping_faults(conf, p.mapping, drv.spec.n_gpus,
                                   config["model"]["n_layers"]) == 0
    # a mapping that is no permutation is a fault
    bad = np.array(p.mapping).ravel()
    bad[0] = bad[1]
    assert plan_ref.mapping_faults(conf, bad, drv.spec.n_gpus, 8) == 1
