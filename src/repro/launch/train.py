"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-7b --smoke --steps 50 --configure

:func:`train` is the whole driver as one function of a ``ModelConfig``;
the CLI below and ``chip_smoke.py`` both call it.  ``--configure`` first
profiles the links between this process's devices
(``profile_bandwidth_live``), runs the Pipette search for that host, and
trains on the mesh the plan prescribes (``mesh_from_plan``): the plan's
microbatch count, its data and model axes, and its pipe axis through
``launch/pipeline.py`` when ``pp > 1``.  Without a plan, or with a
one-device plan, training runs on the default device.  ``--smoke`` trains
the reduced config of the arch so the full driver runs on CPU.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp


@dataclass
class TrainRun:
    """What :func:`train` measured.

    Attributes:
        losses: loss of every step, in order.
        step_s: wall seconds of every step (the step is compiled first).
        compile_s: seconds to lower and compile the train step.
        n_params: parameter count.
    """
    losses: List[float]
    step_s: List[float]
    compile_s: float
    n_params: int


def plan_for_devices(cfg, seq_len: int, global_batch: int, *, seed: int = 0,
                     devices=None):
    """Profile this host's device links and run the Pipette search for them.

    Returns ``(plan, spec, bw)``: the plan, the one-host spec priced as
    TPU v5e chips (:func:`~repro.core.cluster.live_host_spec`), and the
    measured bandwidth matrix.
    """
    from ..core import (Budget, ExhaustiveStrategy, Planner, PlanRequest,
                        PipetteStrategy, SearchSpace, Workload)
    from ..core.cluster import live_host_spec, profile_bandwidth_live

    devices = devices or jax.devices()
    bw = profile_bandwidth_live(devices)
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    spec = live_host_spec(bw, gpu_mem=limit)
    req = PlanRequest(workload=Workload(cfg, seq_len, global_batch),
                      spec=spec, space=SearchSpace(max_cp=1, max_vpp=1),
                      budget=Budget(sa_seconds=60.0, sa_iters=2000,
                                    backend="numpy"),
                      seed=seed)
    # one device has one mapping: rank configurations, anneal nothing
    strategy = PipetteStrategy() if len(devices) > 1 else ExhaustiveStrategy()
    return Planner(strategy).plan(req, bw), spec, bw


def train(cfg, *, steps: int, global_batch: int = 8, seq_len: int = 128,
          n_micro: int = 2, lr: float = 3e-4, seed: int = 0, plan=None,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          resume: bool = False, metrics: Optional[str] = None,
          fail_at: Optional[int] = None, corpus=None,
          log: Callable[[str], None] = print) -> TrainRun:
    """Train ``cfg`` on synthetic data from ``seed``.

    Args:
        cfg: model configuration, at the width and depth to train.
        steps: optimizer steps.
        global_batch / seq_len: tokens per step.
        n_micro: microbatches per step; a ``plan`` replaces it with the
            plan's ``n_mb``.
        lr: peak learning rate of the cosine schedule.
        seed: parameter and data seed.
        plan: a :class:`~repro.core.plan.Plan` for this process's devices;
            ``None`` (or a one-device plan) trains on the default device.
        ckpt_dir: checkpoint directory (``None``: no checkpoints).
        ckpt_every / resume / metrics / fail_at: see
            :class:`~repro.runtime.trainer.TrainLoop`.
        corpus: the :class:`~repro.data.pipeline.SyntheticCorpus` to read
            (default: an unbounded one from ``seed``).
        log: where progress lines go.
    """
    from ..data.pipeline import DataLoader, LoaderConfig, SyntheticCorpus
    from ..optim.adamw import AdamW, cosine_schedule
    from ..runtime.trainer import TrainLoop, TrainLoopConfig

    # warm up over 20 steps, or a fifth of a shorter run
    opt = AdamW(lr=cosine_schedule(lr, min(20, max(1, steps // 5)), steps))
    loader = DataLoader(corpus or SyntheticCorpus(cfg.vocab_size, seed=seed),
                        LoaderConfig(global_batch, seq_len))
    conf = mesh = None
    if plan is not None:
        n_micro = plan.conf.n_mb
    if plan is not None and plan.conf.n_gpus > 1:
        from .mesh import mesh_from_plan
        conf, mesh = plan.conf, mesh_from_plan(plan)
    layout = step_layout(cfg, opt, n_micro=n_micro, conf=conf, mesh=mesh)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(layout.init, **layout.out(layout.params))(key)
    opt_state = jax.jit(opt.init, **layout.out(layout.opt_state))(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))  # repro: noqa DET004 -- .size is an int element count; integer sum is exact in any order
    log(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
        f"batch {global_batch} x seq {seq_len}, {n_micro} microbatches")

    put_batch = layout.put_batch
    t0 = time.perf_counter()
    compiled = layout.jit().lower(
        params, opt_state, put_batch(loader.batch_at(0))).compile()
    compile_s = time.perf_counter() - t0
    log(f"[train] step compiled in {compile_s:.1f}s")

    loop = TrainLoop(
        TrainLoopConfig(total_steps=steps, ckpt_every=ckpt_every,
                        ckpt_dir=ckpt_dir, metrics_path=metrics),
        lambda p, o, b: compiled(p, o, put_batch(b)), loader,
        fail_at_step=fail_at, plan=plan)
    loop.run(params, opt_state, resume=resume)
    losses = [h["loss"] for h in loop.history]
    step_s = [h["dt"] for h in loop.history]
    log(f"[train] {len(losses)} steps in {sum(step_s):.1f}s; "  # repro: noqa DET004 -- a log line's total of wall-clock seconds; order does not matter
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return TrainRun(losses, step_s, compile_s, n_params)


@dataclass(frozen=True)
class StepLayout:
    """A train step and where its arrays live.

    Attributes:
        step: ``(params, opt_state, batch) -> (params, opt_state,
            metrics)``.
        init: ``key -> params`` in the layout ``step`` takes.
        params / opt_state / batch / metrics: shardings, or ``None`` for
            everything on the default device.
    """
    step: Callable
    init: Callable
    params: Any = None
    opt_state: Any = None
    batch: Any = None
    metrics: Any = None

    @staticmethod
    def out(shardings) -> dict:
        """``jax.jit`` kwargs that place a result on ``shardings``."""
        return {} if shardings is None else {"out_shardings": shardings}

    def jit(self):
        """The step, jitted with params and optimizer state donated; a
        sharded step keeps its layout from one step to the next."""
        out = (None if self.params is None
               else (self.params, self.opt_state, self.metrics))
        return jax.jit(self.step, donate_argnums=(0, 1), **self.out(out))

    def put_batch(self, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return (jax.device_put(batch) if self.batch is None
                else jax.device_put(batch, self.batch))


def step_layout(cfg, opt, *, n_micro: int, conf=None,
                mesh=None) -> StepLayout:
    """The train step of ``cfg`` for a plan's ``conf`` lowered to ``mesh``
    (axes ``("pipe", "model", "data")``, :func:`~repro.launch.mesh.
    mesh_from_plan`), or for the default device when ``mesh`` is None.

    ``pp == 1``: GSPMD over the mesh, params sharded by
    :func:`~repro.models.sharding.tree_shardings`, batch over ``"data"``.
    ``pp > 1``: stages over ``"pipe"``, microbatches rotated by
    :mod:`repro.launch.pipeline`, each microbatch split over ``"data"``;
    the pipeline replicates its stages over ``"model"``.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models import model as M
    from ..models.sharding import ShardCtx, tree_shardings
    from ..optim.adamw import AdamWState
    from .pipeline import model_pipeline_loss, model_stage_params
    from .steps import make_train_step

    pipelined = mesh is not None and conf.pp > 1

    def init(key):
        params = M.init_params(cfg, key)
        return model_stage_params(params, conf.pp) if pipelined else params

    if mesh is None:
        return StepLayout(make_train_step(cfg, ShardCtx(), opt,
                                          n_micro=n_micro), init)
    rep = NamedSharding(mesh, P())
    if not pipelined:
        ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model")
        step = make_train_step(cfg, ctx, opt, n_micro=n_micro)
        pshard = tree_shardings(jax.eval_shape(init, jax.random.PRNGKey(0)),
                                cfg, ctx)
        bshard = NamedSharding(mesh, P("data", None))
    else:
        loss_fn = model_pipeline_loss(cfg, mesh, data_axis="data")

        def step(params, opt_state, batch):
            mb = lambda x: x.reshape(n_micro, -1, x.shape[-1])  # noqa: E731
            loss, grads = jax.value_and_grad(loss_fn)(
                params, mb(batch["tokens"]), mb(batch["labels"]))
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, {"loss": loss}

        shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
        pshard = {"stages": jax.tree.map(
                      lambda a: NamedSharding(
                          mesh, P("pipe", *([None] * (a.ndim - 1)))),
                      shapes["stages"]),
                  "shared": jax.tree.map(lambda a: rep, shapes["shared"])}
        bshard = NamedSharding(mesh, P(None, None))
    return StepLayout(step, init, pshard, AdamWState(rep, pshard, pshard),
                      bshard, {"loss": rep})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints/train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--configure", action="store_true",
                    help="profile this host's devices, run the Pipette "
                         "search, and train on the plan's mesh")
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (fault-tolerance demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from .. import configs
    from ..compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()

    plan = None
    if args.configure:
        plan, spec, _ = plan_for_devices(cfg, args.seq_len,
                                         args.global_batch, seed=args.seed)
        print(f"[pipette] profiled {spec.n_gpus} device(s); best config "
              f"{plan.conf} est {plan.latency*1e3:.1f} ms/iter")
        print(f"[pipette] worker dedication (stage-major device ids):\n"
              f"{plan.mapping.reshape(plan.conf.pp, -1)}")

    train(cfg, steps=args.steps, global_batch=args.global_batch,
          seq_len=args.seq_len, n_micro=args.n_micro, lr=args.lr,
          seed=args.seed, plan=plan, ckpt_dir=args.ckpt_dir,
          ckpt_every=args.ckpt_every, resume=args.resume,
          metrics=args.metrics, fail_at=args.fail_at)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
