"""Faults planted in the planned four-chip train cell's program, each of
which its check has to find, and the readings of that check:

    python3 bench/tests/train_plan_faults.py <seconds> <kind>:<seed> ...

runs ``train.qwen2-1.5b.plan4`` once for each ``kind:seed`` in one
process, one after another, through the harness's own ``run_cell``, and
prints each reading as one JSON line.  A kind is ``program`` (the cell as
it is), ``control`` (its lower-precision path) or the name of a fault in
:data:`FAULTS`.  ``bench/tests/test_train_plan_faults.py`` drives the same
:func:`readings` at a small size on four CPU devices."""
import dataclasses
import json
import os
import sys
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.drivers import train_plan                      # noqa: E402
from bench.reference import dense_ref                     # noqa: E402

CELL = "train.qwen2-1.5b.plan4"


class HalfBatch(train_plan.Cell):
    """Half of every batch left out, the mean taken over the rest: the
    rows of the second half replaced by those of the first, so that the
    step keeps its shapes."""

    def compile_step(self):
        real = super().compile_step()

        def step(params, opt_state, b):
            return real(params, opt_state, {
                k: np.concatenate([v[:len(v) // 2]] * 2)
                for k, v in b.items()})
        return step


class StateUnchanged(train_plan.Cell):
    """A step that returns the state it was given: the initial state, made
    again from the seed after each step."""

    def compile_step(self):
        real = super().compile_step()

        def step(params, opt_state, b):
            _, _, loss = real(params, opt_state, b)
            params = self.init(dense_ref.key(self.ctx.seed))
            return params, self.opt_init(params), loss
        return step


class PipeSumDropped(train_plan.Cell):
    """The sum over the pipe axis of the shared leaves' gradient left out:
    the embedding, the final norm and the tied head get the first stage's
    part alone (the embedding's lookup), as that stage holds it, and not
    the last stage's (the final norm and the head).  Needs a plan with
    more than one stage."""

    def compile_step(self):
        import jax
        import jax.numpy as jnp
        from repro.launch.pipeline import pipeline_loss_fn
        from repro.models import model as M
        from repro.models.sharding import ShardCtx
        from repro.models.transformer import run_stack

        cfg, n_mb, pp = self.cfg, self.plan.conf.n_mb, self.plan.conf.pp
        assert self.mesh is not None and pp > 1, self.plan.conf
        stage_cfg = cfg.replace(n_layers=cfg.n_layers // pp)

        def embed_fn(shared, tokens):
            return M.embed_inputs(shared, cfg, tokens)[0]

        def stage_fn(stage, x):
            b, s, _ = x.shape
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            return run_stack(x, {"layers": stage}, stage_cfg, ShardCtx(),
                             pos)[0]

        def head_loss_fn(shared, x, labels):
            return M.head_loss(jax.lax.stop_gradient(shared), cfg, x, labels)

        loss_fn = pipeline_loss_fn(embed_fn, stage_fn, head_loss_fn,
                                   self.mesh, data_axis="data")

        def train_step(params, opt_state, batch):
            mb = lambda x: x.reshape(n_mb, -1, x.shape[-1])  # noqa: E731
            loss, grads = jax.value_and_grad(loss_fn)(
                params, mb(batch["tokens"]), mb(batch["labels"]))
            params, opt_state = self.opt.update(grads, opt_state, params)
            return params, opt_state, {"loss": loss}

        self.layout = dataclasses.replace(self.layout, step=train_step)
        return super().compile_step()


FAULTS = {"half_batch": HalfBatch, "state_unchanged": StateUnchanged,
          "pipe_sum_dropped": PipeSumDropped}


def readings(files, runs, seconds: float, devices):
    """For each ``(kind, seed)`` of ``runs``, the cell ``files`` (as
    ``bench.run.cell_files`` gives them) run once on ``devices``: a dict
    of the kind, the seed, ``correct``, the compared numbers and the
    run's seconds."""
    from bench import run as R

    bm, entry, config, traffic = files
    for kind, seed in runs:
        drv = (types.SimpleNamespace(Cell=FAULTS[kind]) if kind in FAULTS
               else None)
        t = time.perf_counter()
        res = R.run_cell(bm, entry, config, traffic, seed=seed,
                         seconds=seconds, trace=False, devices=devices,
                         control=kind == "control", t_start=t, driver=drv)
        yield {"kind": kind, "seed": seed, "correct": res["correct"],
               "checks": res["checks"], "metrics": res["metrics"],
               "seconds": time.perf_counter() - t}


def main(seconds: float, runs):
    """Prints each reading, and after the first (a fresh process's) each
    chip's memory statistics."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from bench import run as R

    devices = jax.devices()
    assert devices[0].platform == "tpu", "needs a TPU"
    for i, reading in enumerate(readings(R.cell_files(CELL), runs, seconds,
                                         devices)):
        print(json.dumps(reading), flush=True)
        if i == 0:
            print(json.dumps({"memory_stats": [d.memory_stats()
                                               for d in devices]}),
                  flush=True)


if __name__ == "__main__":
    main(float(sys.argv[1]),
         [(k, int(s)) for k, s in (a.split(":") for a in sys.argv[2:])])
