"""Plan cells: one client asks ``Planner(PipetteStrategy()).plan`` for one
plan after another (closed loop) on a fleet and link matrix generated
from the seed.  Every plan completed in the window is checked against
the plain reference afterwards, and against the plan that the program's
NumPy backend makes of the same request."""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from ..reference import plan_ref
from ..traffic.fleet import bandwidth_matrix, node_tiers


def _throughput(tier: dict) -> float:
    return tier["flops"] * tier["efficiency"]


class Cell:
    """One plan cell; ``ctx`` is the harness's :class:`~bench.run.Context`."""

    #: host spans the harness names idle gaps by
    spans = ("plan.request",)

    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx.config, ctx.traffic
        self.plans = []             # (start_s, end_s, plan) of the window

    # -- inputs -----------------------------------------------------------
    def _inputs(self):
        from repro.core import Workload
        from repro.core.cluster import ClusterSpec, DeviceTier
        from repro.models.config import ModelConfig

        fleet, model, job = (self.config[k] for k in ("fleet", "model", "job"))
        tiers = fleet["tiers"]
        nt = node_tiers(fleet["n_nodes"], [t["fraction"] for t in tiers],
                        fleet["layout_seed"])
        ref = max(tiers, key=_throughput)
        self.spec = ClusterSpec(
            self.config["name"], fleet["n_nodes"],
            gpus_per_node=fleet["gpus_per_node"], intra_bw=fleet["intra_bw"],
            inter_bw=fleet["inter_bw"], gpu_flops=ref["flops"],
            gpu_mem=ref["mem"], efficiency=ref["efficiency"],
            heterogeneity=fleet["heterogeneity"],
            slow_frac=fleet["slow_frac"], seed=fleet["layout_seed"],
            tiers=tuple(DeviceTier(t["flops"], t["mem"], t["efficiency"],
                                   t["name"]) for t in tiers),
            node_tiers=tuple(int(t) for t in nt))
        self.bw = bandwidth_matrix(fleet, self.ctx.seed)
        thru = np.array([_throughput(t) for t in tiers])
        self.slow = (_throughput(ref) / thru[nt]).repeat(fleet["gpus_per_node"])
        self.workload = Workload(
            ModelConfig(name=self.config["name"], family="dense",
                        n_layers=model["n_layers"], d_model=model["d_model"],
                        n_heads=model["n_heads"],
                        n_kv_heads=model.get("n_kv_heads", model["n_heads"]),
                        d_ff=model["d_ff"], vocab_size=model["vocab_size"],
                        head_dim=model.get("head_dim", 0)),
            job["seq"], job["bs_global"], job["grad_bytes"])

    def request(self, k: int):
        from repro.core import Budget, PlanRequest, SearchSpace
        t = self.traffic
        sa_seed = int(np.random.default_rng([self.ctx.seed, k]).integers(2**31))
        return PlanRequest(
            workload=self.workload, spec=self.spec,
            space=SearchSpace(max_tp=t["max_tp"], max_cp=t["max_cp"],
                              fixed_micro=t["fixed_micro"]),
            budget=Budget(sa_seconds=t["sa_seconds"], sa_iters=t["sa_iters"],
                          n_chains=t["n_chains"], sa_topk=t["sa_topk"],
                          backend=t["backend"]),
            seed=sa_seed)

    def plan(self, k: int, backend: str | None = None):
        """The program's plan of request ``k``; ``backend`` overrides the
        traffic's annealer backend."""
        from repro.core import Planner, PipetteStrategy
        req = self.request(k)
        if backend is not None:
            req = dataclasses.replace(req, budget=dataclasses.replace(
                req.budget, backend=backend))
        return Planner(PipetteStrategy()).plan(req, self.bw)

    # -- phases -----------------------------------------------------------
    def setup(self):
        self._inputs()
        p = self.plan(0)                # warms every shape the window uses
        self.ctx.log(f"set-up plan: {p.conf} latency {p.latency!r} s")
        self.ctx.log("set-up plan phases: " + ", ".join(
            f"{k} {v:.4f}" for k, v in p.result.overhead.as_dict().items()
            if k.endswith("_s")))

    def window(self, seconds: float) -> dict:
        """Plans back to back until ``seconds`` have passed; the plan in
        flight at the deadline runs to its end and counts."""
        t0 = time.perf_counter()
        k = 0
        while True:
            k += 1
            s = time.perf_counter()
            with self.ctx.span("plan.request"):
                p = self.plan(k)
            e = time.perf_counter()
            self.plans.append((s, e, p))
            self.ctx.log(f"plan {k}: {e - s:.4f} s {p.conf} latency "
                         f"{p.latency!r} s")
            if e - t0 >= seconds:
                break
        window_s = self.plans[-1][1] - t0
        return {"window_s": window_s, "attempted": len(self.plans),
                "metrics": {"plan_s": window_s / len(self.plans)}}

    def layer_record(self) -> dict:
        """Per-plan program timings for the per-layer readers."""
        return {"overheads": [p.result.overhead for _, _, p in self.plans],
                "n": len(self.plans)}

    # -- correctness ------------------------------------------------------
    @staticmethod
    def _conf(c) -> dict:
        return {"pp": c.pp, "tp": c.tp, "cp": c.cp, "dp": c.dp,
                "bs_micro": c.bs_micro, "bs_global": c.bs_global,
                "vpp": c.vpp}

    def answer(self, c) -> float:
        """The latency a candidate of the plan claims; the control puts the
        float32 reference in the program's place."""
        if self.ctx.control:
            return plan_ref.latency(self._conf(c.conf), c.mapping, self.bw,
                                    self.config["model"], self.config["job"],
                                    self.config["fleet"], self.slow,
                                    np.float32)
        return float(c.latency)

    def score(self, c) -> float:
        """The float64 reference's latency of candidate ``c``."""
        model, job, fleet = (self.config[k] for k in ("model", "job", "fleet"))
        return plan_ref.latency(self._conf(c.conf), c.mapping, self.bw,
                                model, job, fleet, self.slow)

    def check(self):
        """``(numbers, failed)`` over every plan of the window and every
        candidate it ranks (the best and its fallbacks): the widest
        relative gap between a candidate's latency and the float64
        reference's score of its mapping, the broken guarantees of the
        configurations and mappings, the plan verifier's errors, and how
        much worse than the NumPy-backend plan of the same request the
        program's choice is, both scored by the reference."""
        from repro.analysis import verify_plan_dict
        model = self.config["model"]
        worst, faults, verr, choice, failed = 0.0, 0, 0, -np.inf, 0
        limits = self.config["limits"]
        for k, (_, _, p) in enumerate(self.plans, start=1):
            bad = sum(i.severity == "error" for i in verify_plan_dict(
                p.to_json_dict(), spec=self.spec, bw=self.bw))
            verr += bad
            for c in p.ranked:
                conf = self._conf(c.conf)
                ref = self.score(c)
                rel = abs(self.answer(c) - ref) / ref
                nf = plan_ref.mapping_faults(conf, c.mapping,
                                             self.spec.n_gpus,
                                             model["n_layers"])
                worst, faults = max(worst, rel), faults + nf
                bad += rel > limits["plan_rel_gap"] or nf
            other = self.plan(k, backend="numpy")
            mine, theirs = self.score(p.ranked[0]), self.score(other.ranked[0])
            gap = (mine - theirs) / theirs
            self.ctx.log(f"plan {k}: {p.conf} scores {mine!r} s, numpy-backend "
                         f"{other.conf} {theirs!r} s, choice gap {gap!r}")
            choice = max(choice, gap)
            bad += gap > limits["choice_gap"]
            failed += bool(bad)
        return ([("plan_rel_gap", worst, limits["plan_rel_gap"]),
                 ("plan_faults", faults, 0), ("verifier_errors", verr, 0),
                 ("choice_gap", choice, limits["choice_gap"])], failed)
