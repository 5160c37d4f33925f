"""Train cells on the planned path: the program plans the layout for the
cell's chips (``launch.train.plan_for_devices``, from the configuration's
recorded link matrix, so every run plans the same layout), lowers it
(``plan_layout``: ``mesh_from_plan`` then ``step_layout`` with the plan's
microbatch count) and runs that step, as ``launch.train`` does with
``--configure``.

Batches, window and leaf-norm check are those of :mod:`.train`.  The
reference is :mod:`..reference.dense_ref_staged`, the same step with its
layers placed whole over the cell's chips, run after the program's state
is freed.  Beside the leaf norms, set-up keeps the program's first
clipped gradient whole on the host (off Adam's first moment after the
first step), and the check compares it with the reference's element by
element: each leaf's distance over the reference's norm.  The plan
itself is checked by the plan verifier."""
from __future__ import annotations

import gc

import numpy as np

from ..reference import dense_ref, dense_ref_staged
from . import train
from .train import CHECKED_STEPS

#: the program's span around planning, whose seconds the record keeps
PLAN_SPAN = "/pipette/span/train.plan"
#: ``(event, seconds)`` of every duration event since :func:`_listen`
_SPANS: list = []
_listening = False


def _listen():
    """Record JAX's duration events into ``_SPANS`` (once a process)."""
    global _listening
    import jax
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, d, **kw: _SPANS.append((e, d)))
        _listening = True


def link_matrix(config: dict) -> np.ndarray:
    """The recorded bytes/s matrix; its null diagonal reads as ``inf``."""
    return np.array([[np.inf if v is None else float(v) for v in row]
                     for row in config["bandwidth"]["matrix"]])


class Cell(train.Cell):

    def _program(self):
        import jax
        from repro.launch.pipeline import model_stage_params
        from repro.launch.train import plan_for_devices, plan_layout
        from repro.models.config import ModelConfig
        from repro.optim.adamw import AdamW

        m, o = self.model, self.config["optimizer"]
        self.dtype = self.config["control_dtype" if self.ctx.control
                                 else "dtype"]
        self.cfg = ModelConfig(
            name=self.config["name"], family="dense",
            n_layers=m["n_layers"], d_model=m["d_model"],
            n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
            d_ff=m["d_ff"], vocab_size=m["vocab_size"],
            head_dim=m["head_dim"], qkv_bias=m["qkv_bias"],
            rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
            tie_embeddings=m["tie_embeddings"], dtype=self.dtype)
        self.opt = AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         grad_clip=o["grad_clip"])

        _listen()
        first = len(_SPANS)
        self.bw = link_matrix(self.config)
        self.plan, self.spec, _ = plan_for_devices(
            self.cfg, self.seq, self.rows, seed=self.ctx.seed,
            devices=self.ctx.devices, bw=self.bw)
        self.plan_s = sum(d for e, d in _SPANS[first:] if e == PLAN_SPAN)
        p = self.plan
        self.ctx.log(f"plan: {p.conf} mapping {p.mapping.tolist()} "
                     f"latency {p.latency!r} s, planned in "
                     f"{self.plan_s:.4f} s")
        self.mesh, self.layout = plan_layout(self.cfg, self.opt, p)
        pp = p.conf.pp if self.mesh is not None else 1

        def init(key):
            params = dense_ref.init(m, key, dtype=self.dtype)
            return model_stage_params(params, pp) if pp > 1 else params

        self.init = jax.jit(init, **self.layout.out(self.layout.params))
        self.params = self.init(dense_ref.key(self.ctx.seed))
        self.opt_init = jax.jit(self.opt.init, **self.layout.out(
            self.layout.opt_state))
        self.opt_state = self.opt_init(self.params)
        self.step = self._keeping_first_gradient(self.compile_step())

    def compile_step(self):
        lay = self.layout
        exe = lay.compile(self.params, self.opt_state,
                          lay.put_batch(self.batch(1)))

        def step(params, opt_state, b):
            params, opt_state, met = exe(params, opt_state, lay.put_batch(b))
            return params, opt_state, met["loss"]
        return step

    def _keeping_first_gradient(self, step):
        """``step``, which at its first call also keeps on the host, as
        ``grad1`` in :func:`dense_ref.init`'s layout, the clipped gradient
        that Adam's first moment holds in the state the step returns."""
        import jax

        def first(params, opt_state, b):
            self.step = step
            params, opt_state, loss = step(params, opt_state, b)
            scale = np.float32(1 / (1 - self.config["optimizer"]["b1"]))
            m = jax.tree.map(lambda x: np.asarray(x, np.float32) * scale,
                             opt_state.m)
            self.grad1 = (m if "stages" not in m else dict(
                m["shared"], layers={k: v.reshape(-1, *v.shape[2:])
                                     for k, v in m["stages"].items()}))
            return params, opt_state, loss
        return first

    def layer_record(self) -> dict:
        return dict(super().layer_record(), plan_s=self.plan_s,
                    plan_latency_s=self.plan.latency, steps=self.steps,
                    window_s=self.window_s)

    # -- correctness ------------------------------------------------------
    def _worst_leaf(self, what, names, diff, ref, keep) -> float:
        """The largest of ``diff`` over the leaves ``keep``, each over the
        reference's norm of that leaf or of the median leaf, whichever is
        larger (:func:`train._leaf_gap`'s rule), logged with its leaf."""
        d = np.array([diff[k] for k in names])
        r = np.array([ref[k] for k in names])
        gaps = np.where(keep, d / np.maximum(r, np.median(r[keep])), 0.0)
        w = int(np.argmax(gaps))
        self.ctx.log(f"{what} {float(gaps[w])!r} at leaf {names[w]}: "
                     f"difference {float(d[w])!r}, reference norm "
                     f"{float(r[w])!r}")
        return float(gaps[w])

    def _verifier_errors(self) -> int:
        from repro.analysis import verify_plan_dict
        return sum(i.severity == "error" for i in verify_plan_dict(
            self.plan.to_json_dict(), spec=self.spec, bw=self.bw))

    def check(self):
        """:meth:`train.Cell.check`'s comparison against the staged
        reference, the first gradient's distance from the reference's,
        and the plan verifier's errors.  Each number is compared where the
        configuration gives it a limit and logged with its worst leaf
        everywhere.  The leaf norms of the first gradient do not tell the
        precision apart at 28 layers: the program's one-pass bfloat16
        matmuls lift them as far as the bfloat16 control does (PERF.md),
        so this configuration compares the gradient by its distance."""
        def peaks(when):
            gib = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   / 2**30 for d in self.ctx.devices]
            self.ctx.log(f"peak device memory by chip {when}: "
                         + ", ".join(f"{g:.3f} GiB" for g in gib))
        peaks("after the program")
        verr = self._verifier_errors()
        del self.params, self.opt_state, self.step
        gc.collect()
        ref_loss, ref_g1, ref_dp, dist = dense_ref_staged.follow(
            self.model, self.config["optimizer"], self.ctx.seed,
            [self.batch(k) for k in range(1, CHECKED_STEPS + 1)],
            self.config["reference_rows"], self.ctx.devices, grad=self.grad1)
        del self.grad1
        peaks("after the reference")
        loss_gap = max(abs(a - b) / b for a, b in zip(self.losses, ref_loss))
        self.ctx.log(f"reference losses {ref_loss}; loss gap {loss_gap!r} "
                     "(not compared)")
        names = sorted(ref_g1)
        g_ref = np.array([ref_g1[k] for k in names])
        every = np.ones(len(names), bool)
        moved = g_ref >= 1e-3 * np.median(g_ref)
        found = {
            "grad_norm_gap": self._worst_leaf(
                "grad_norm_gap", names,
                {k: abs(self.g1[k] - ref_g1[k]) for k in names}, ref_g1,
                every),
            "grad_dist_gap": self._worst_leaf("grad_dist_gap", names, dist,
                                              ref_g1, every),
            "update_norm_gap": self._worst_leaf(
                "update_norm_gap", names,
                {k: abs(self.dp[k] - ref_dp[k]) for k in names}, ref_dp,
                moved)}
        lim = self.config["limits"]
        nums = [(k, v, lim[k]) for k, v in found.items() if k in lim]
        nums.append(("verifier_errors", verr, 0))
        return nums, int(any(v > l for _, v, l in nums))
