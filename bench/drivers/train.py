"""Train cells: the program's compiled train step (``launch.train.
step_layout``), driven step after step on a fresh synthetic batch each,
as its own trainer drives it (the loss is read back every step).

Set-up makes the weights from the seed in one jitted call, compiles the
step and runs its first three steps, which the plain reference follows
after the window: each step's loss, the leaf norms of the first clipped
gradient (read off Adam's first moment after step 1) and the leaf norms
of the parameters' change after step 3."""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from .. import flops as F
from ..reference import dense_ref
from ..traffic.tokens import batch

#: steps that set-up runs and the reference follows
CHECKED_STEPS = 3


class Cell:
    spans = ("train.batch", "train.step")

    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx.config, ctx.traffic
        self.model = dense_ref.arch(self.config)
        t = self.traffic
        self.rows, self.seq = t["global_batch"], t["seq"]

    def batch(self, step: int) -> dict:
        return batch(self.ctx.seed, step, self.rows, self.seq,
                     self.model["vocab_size"])

    # -- the program ------------------------------------------------------
    def _program(self):
        import jax
        from repro.launch.train import step_layout
        from repro.models.config import ModelConfig
        from repro.optim.adamw import AdamW

        m, o = self.model, self.config["optimizer"]
        # the control is the program's own lower-precision path
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
        self.layout = step_layout(self.cfg, self.opt,
                                  n_micro=self.traffic["n_micro"])
        self.init = jax.jit(functools.partial(dense_ref.init, m,
                                              dtype=self.dtype),
                            **self.layout.out(self.layout.params))
        self.params = self.init(dense_ref.key(self.ctx.seed))
        self.opt_state = jax.jit(self.opt.init, **self.layout.out(
            self.layout.opt_state))(self.params)
        self.step = self.compile_step()

    def compile_step(self):
        """The step as the window calls it: ``(params, opt_state, host
        batch) -> (params, opt_state, loss)``."""
        lay = self.layout
        exe = lay.jit().lower(self.params, self.opt_state,
                              lay.put_batch(self.batch(1))).compile()

        def step(params, opt_state, b):
            params, opt_state, met = exe(params, opt_state, lay.put_batch(b))
            return params, opt_state, met["loss"]
        return step

    # -- phases -----------------------------------------------------------
    def setup(self):
        self._program()
        b1 = self.config["optimizer"]["b1"]
        self.losses = []
        for k in range(1, CHECKED_STEPS + 1):
            self.params, self.opt_state, loss = self.step(
                self.params, self.opt_state, self.batch(k))
            self.losses.append(float(loss))
            if k == 1:
                self.g1 = {k: v / (1 - b1) for k, v in
                           dense_ref.leaf_norms(self.opt_state.m).items()}
        p0 = self.init(dense_ref.key(self.ctx.seed))
        self.dp = dense_ref.diff_norms(self.params, p0)
        del p0
        self.ctx.log(f"set-up steps: losses {self.losses}")

    def window(self, seconds: float) -> dict:
        """Steps until ``seconds`` have passed; the step in flight at the
        deadline completes and counts."""
        t0 = time.perf_counter()
        k, n, nonfinite = CHECKED_STEPS, 0, 0
        while True:
            k += 1
            with self.ctx.span("train.batch"):
                b = self.batch(k)
            with self.ctx.span("train.step"):
                self.params, self.opt_state, loss = self.step(
                    self.params, self.opt_state, b)
                loss = float(loss)
            n += 1
            nonfinite += not np.isfinite(loss)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        self.steps, self.window_s = n, window_s
        self.ctx.log(f"window: {n} steps in {window_s:.4f} s, last loss "
                     f"{loss!r}")
        return {"window_s": window_s, "attempted": n, "failed": nonfinite,
                "metrics": {"tokens_per_s": n * self.rows * self.seq
                            / window_s}}

    def layer_record(self) -> dict:
        return {"flops_per_token": F.train_flops_per_token(self.model,
                                                           self.seq),
                "tokens_per_s": self.steps * self.rows * self.seq
                / self.window_s}

    # -- correctness ------------------------------------------------------
    def check(self):
        """``(numbers, failed)``: the worst leaf's gap of the first
        gradient's norm and of the parameters' change after three steps,
        each against the reference's norm of that leaf or of the median
        leaf, whichever is larger.  The widest relative gap of the three
        losses is printed and not compared: the lower-precision control
        reads it no higher than sound runs do."""
        del self.params, self.opt_state, self.step
        gc.collect()
        ref_loss, ref_g1, ref_dp = dense_ref.follow(
            self.model, self.config["optimizer"], self.ctx.seed,
            [self.batch(k) for k in range(1, CHECKED_STEPS + 1)],
            self.config["reference_rows"])
        loss_gap = max(abs(a - b) / b for a, b in zip(self.losses, ref_loss))
        self.ctx.log(f"reference losses {ref_loss}; loss gap {loss_gap!r} "
                     "(not compared)")
        names = sorted(ref_g1)
        g_ref = np.array([ref_g1[k] for k in names])
        g_gap = _leaf_gap([self.g1[k] for k in names], g_ref,
                          np.ones(len(names), bool))
        # leaves whose reference gradient is nought to rounding move under
        # Adam by round-off alone: left out of the change
        moved = g_ref >= 1e-3 * np.median(g_ref)
        dp_gap = _leaf_gap([self.dp[k] for k in names],
                           [ref_dp[k] for k in names], moved)
        lim = self.config["limits"]
        nums = [("grad_norm_gap", g_gap, lim["grad_norm_gap"]),
                ("update_norm_gap", dp_gap, lim["update_norm_gap"])]
        return nums, int(any(v > l for _, v, l in nums))


def _leaf_gap(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog), np.asarray(ref)
    floor = np.median(ref[keep])
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    return float(gaps[keep].max())
