"""The planned four-chip train cell's readers on synthetic runs: the
collective share from a four-device trace summary, the planner's latency
error and the plan span's seconds from the cell driver's record."""
from pathlib import Path

import pytest

from bench import trace as T
from bench.run import load_module

METRICS = Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000                                  # ns


def read(metric, run):
    return load_module(METRICS / f"{metric}.py").read(run)


def four_chips() -> T.Trace:
    """A 100 ms window on four chips: each runs a 60 ms fusion, then a
    collective-permute (chip k: k + 1 ms) and a 10 ms all-reduce that
    overlaps 5 ms of compute."""
    tr = T.Trace(host=[(0, 100 * MS, T.WINDOW_SPAN)])
    for k in range(4):
        tr.devices[f"{T.DEVICE_PREFIX}{k}"] = [
            (0, 60 * MS, "%fusion.1 = f32[8]{0} fusion(...)"),
            (60 * MS, (k + 1) * MS,
             "%collective-permute.2 = f32[8]{0} collective-permute(...)"),
            (70 * MS, 10 * MS, "%fusion.3 = f32[8]{0} fusion(...)"),
            (75 * MS, 10 * MS, "%all-reduce.4 = f32[8]{0} all-reduce(...)"),
        ]
    return tr


def test_collective_share_averages_the_four_chips():
    summary = T.summarize(four_chips())
    assert summary["n_devices"] == 4
    # chip k: (k + 1) ms of permute and 10 ms of all-reduce
    assert summary["collective_s"] == pytest.approx(0.0125)
    got = read("train4.collective_share", {"trace": summary})
    assert got == pytest.approx(12.5)


def test_collective_share_reads_zero_without_collectives():
    tr = four_chips()
    for k, evs in tr.devices.items():
        tr.devices[k] = [e for e in evs if not T.is_collective(e[2])]
    got = read("train4.collective_share", {"trace": T.summarize(tr)})
    assert got == 0.0


def test_plan_error_is_step_time_against_predicted_latency():
    record = {"window_s": 50.0, "steps": 100, "plan_latency_s": 0.4}
    assert read("train4.plan_error", {"record": record}) == pytest.approx(25.0)
    record["plan_latency_s"] = 0.625
    assert read("train4.plan_error", {"record": record}) == pytest.approx(20.0)
    # a driver without a plan has nothing to read
    assert read("train4.plan_error",
                {"record": {"window_s": 50.0, "steps": 100}}) is None


def test_plan_s_reads_the_recorded_span():
    assert read("train4.plan_s", {"record": {"plan_s": 1.25}}) == 1.25
    assert read("train4.plan_s", {"record": {}}) is None
