"""Regenerate ``golden_plan_v6.json`` — the checked-in Plan JSON fixture.

The fixture is the serialized Plan of a fixed, iteration-bound (fully
deterministic) Pipette search on the mixed A100/V100 16x1 cluster, so it
exercises the heterogeneous tier-provenance fields.  Regenerate ONLY on an
*intentional* schema change, together with a PLAN_SCHEMA_VERSION bump
(tests/test_plan_golden.py refuses shape changes without one):

    PYTHONPATH=src python tests/data/gen_golden_plan.py
"""
import pathlib

from repro.core import (Budget, Planner, PlanRequest, PipetteStrategy,
                        SearchSpace, Workload, profile_bandwidth)
from repro.core.cluster import A100_TIER, V100_TIER, mixed_fleet_spec
from repro.models.config import ModelConfig

OUT = pathlib.Path(__file__).parent / "golden_plan_v6.json"

GPT = ModelConfig(name="g12", family="dense", n_layers=12, d_model=1024,
                  n_heads=16, n_kv_heads=16, d_ff=4096, vocab_size=32000)
SPEC = mixed_fleet_spec("mixed-a100-v100-16x1", 16, (A100_TIER, V100_TIER),
                        (0.5, 0.5), gpus_per_node=1, seed=47)
REQ = PlanRequest(workload=Workload(GPT, 2048, 32), spec=SPEC,
                  space=SearchSpace(max_micro=2),
                  budget=Budget(sa_seconds=60.0, sa_iters=50, sa_topk=2),
                  seed=9)


def main() -> None:
    bw, _ = profile_bandwidth(SPEC)
    plan = Planner(PipetteStrategy()).plan(REQ, bw)
    plan.save(OUT)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
