"""Golden-file contract for the serialized Plan schema (version 6).

Three locks:

1. the checked-in fixture (``tests/data/golden_plan_v6.json``) loads and
   re-serializes **byte-for-byte** — the wire format cannot drift silently;
2. regenerating the same request live reproduces the fixture bytes —
   plans are deterministic artifacts, not process-local snapshots;
3. the serialized *shape* (every key path) is pinned: adding/removing/
   renaming any field fails here until ``PLAN_SCHEMA_VERSION`` is bumped
   (and the fixture regenerated via ``tests/data/gen_golden_plan.py``).
"""
import json
from pathlib import Path

import pytest

from repro.core import Plan, PlanLoadError, profile_bandwidth
from repro.core.plan import PLAN_SCHEMA_VERSION

GOLDEN = Path(__file__).parent / "data" / "golden_plan_v6.json"

#: Every key path of the version-6 schema (the paths of version 5; 6
#: changed what ``provenance.budget.backend`` may hold).  ``[]`` marks
#: list elements.
#: CHANGING THIS SET == CHANGING THE WIRE FORMAT: bump PLAN_SCHEMA_VERSION,
#: regenerate the fixture, and rename it (golden_plan_v<N>.json).
SCHEMA_V6_PATHS = frozenset({
    "best.conf.bs_global", "best.conf.bs_micro", "best.conf.cp",
    "best.conf.dp", "best.conf.pp", "best.conf.tp", "best.conf.vpp",
    "best.latency",
    "best.mapping.data[]", "best.mapping.dtype", "best.mapping.shape[]",
    "best.mem_pred", "best.partition", "best.schedule",
    "overhead.n_candidates", "overhead.n_enumerated",
    "overhead.sa_accepted", "overhead.sa_accepted_to_best",
    "provenance.bs_global",
    "provenance.budget.backend", "provenance.budget.hierarchical",
    "provenance.budget.n_chains",
    "provenance.budget.sa_iters", "provenance.budget.sa_seconds",
    "provenance.budget.sa_topk", "provenance.budget.warm_start",
    "provenance.bw_digest",
    "provenance.cluster", "provenance.estimator", "provenance.lineage",
    "provenance.model",
    "provenance.n_gpus", "provenance.seed", "provenance.seq",
    "provenance.space.fixed_micro", "provenance.space.max_cp",
    "provenance.space.max_micro", "provenance.space.max_tp",
    "provenance.space.max_vpp", "provenance.space.partition",
    "provenance.tiers.digest", "provenance.tiers.node_tiers[]",
    "provenance.tiers.tiers[].efficiency", "provenance.tiers.tiers[].flops",
    "provenance.tiers.tiers[].mem", "provenance.tiers.tiers[].name",
    "ranked[].conf.bs_global", "ranked[].conf.bs_micro", "ranked[].conf.cp",
    "ranked[].conf.dp", "ranked[].conf.pp", "ranked[].conf.tp",
    "ranked[].conf.vpp",
    "ranked[].latency", "ranked[].mapping.data[]", "ranked[].mapping.dtype",
    "ranked[].mapping.shape[]", "ranked[].mem_pred",
    "ranked[].partition", "ranked[].schedule",
    "strategy", "version",
})


def _paths(o, pre=""):
    out = set()
    if isinstance(o, dict):
        for k, v in o.items():
            out |= _paths(v, f"{pre}.{k}" if pre else k)
    elif isinstance(o, list):
        for v in o[:1]:
            out |= _paths(v, pre + "[]")
    else:
        out.add(pre)
    return out


def test_golden_plan_loads_and_roundtrips_byte_for_byte():
    text = GOLDEN.read_text()
    plan = Plan.load(GOLDEN)
    assert plan.to_json() == text
    assert plan.feasible
    # tier provenance (the v2 addition) is populated in the fixture
    tiers = plan.provenance.tiers
    assert tiers is not None and len(tiers["digest"]) == 64
    assert {t["name"] for t in tiers["tiers"]} == {"a100", "v100"}
    # the v3 additions, as v6 reads them: the executor that ran (the
    # Budget default) and hierarchical search left on auto
    assert plan.provenance.budget.backend == "numpy"
    assert plan.provenance.budget.hierarchical is None
    # the v4 additions: partition/schedule provenance (uniform search →
    # no partition, plain 1F1B) and the vpp degree on every conf
    assert plan.partition is None
    assert plan.schedule == "1f1b"
    assert plan.conf.vpp == 1
    assert plan.provenance.space.partition == "uniform"
    assert plan.provenance.space.max_vpp == 1
    # the v5 additions: cold search → no warm-start seed, no serving
    # lineage; the accepted-move counters are recorded and consistent
    assert plan.provenance.budget.warm_start is None
    assert plan.provenance.lineage is None
    assert plan.overhead.sa_accepted >= plan.overhead.sa_accepted_to_best >= 0


def test_golden_plan_reproduced_live_byte_for_byte(tmp_path):
    """The same request regenerated today must produce the exact fixture
    bytes — the Plan artifact is deterministic end to end."""
    from tests.data.gen_golden_plan import REQ, SPEC
    from repro.core import Planner, PipetteStrategy

    bw, _ = profile_bandwidth(SPEC)
    plan = Planner(PipetteStrategy()).plan(REQ, bw)
    assert plan.to_json() == GOLDEN.read_text()


def test_schema_version_must_bump_on_shape_change():
    live = _paths(json.loads(GOLDEN.read_text()))
    if PLAN_SCHEMA_VERSION == 6:
        assert live == SCHEMA_V6_PATHS, (
            "the serialized Plan shape changed but PLAN_SCHEMA_VERSION is "
            "still 6 — bump it, regenerate tests/data/golden_plan_v6.json "
            "under the new name, and update SCHEMA_V6_PATHS\n"
            f"added: {sorted(live - SCHEMA_V6_PATHS)}\n"
            f"removed: {sorted(SCHEMA_V6_PATHS - live)}")
    else:
        pytest.fail(
            "PLAN_SCHEMA_VERSION moved past 6: retire this guard by "
            "pinning the new shape and fixture (see gen_golden_plan.py)")


def test_loader_rejects_other_schema_versions():
    d = json.loads(GOLDEN.read_text())
    for bad in (1, 2, 3, 4, 5, PLAN_SCHEMA_VERSION + 1, None):
        d["version"] = bad
        with pytest.raises(PlanLoadError, match="schema version"):
            Plan.from_json_dict(d)
        # PlanLoadError subclasses ValueError, so pre-existing callers
        # catching the historical type keep working
        with pytest.raises(ValueError, match="schema version"):
            Plan.from_json_dict(d)


def test_load_errors_are_typed_and_carry_the_path(tmp_path):
    bad_json = tmp_path / "corrupt.plan.json"
    bad_json.write_text("{not json")
    with pytest.raises(PlanLoadError, match="not valid JSON") as ei:
        Plan.load(bad_json)
    assert ei.value.path == str(bad_json)

    wrong_version = tmp_path / "old.plan.json"
    d = json.loads(GOLDEN.read_text())
    d["version"] = 3
    wrong_version.write_text(json.dumps(d))
    with pytest.raises(PlanLoadError, match="schema version") as ei:
        Plan.load(wrong_version)
    assert ei.value.path == str(wrong_version)

    broken = tmp_path / "broken.plan.json"
    d = json.loads(GOLDEN.read_text())
    del d["provenance"]["bw_digest"]
    broken.write_text(json.dumps(d))
    with pytest.raises(PlanLoadError, match="structurally invalid") as ei:
        Plan.load(broken)
    assert ei.value.path == str(broken)
