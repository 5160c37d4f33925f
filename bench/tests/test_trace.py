"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``: three 2048^3 matmul steps, each followed by 20 ms
of host-only ``train.batch``, then one call of each group-reduce kernel)."""
from pathlib import Path

import pytest

from bench import trace as T
from bench.run import load_module

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
METRICS = Path(__file__).resolve().parents[1] / "metrics"


@pytest.fixture(scope="module")
def summary():
    return T.summarize(T.load(str(DATA)), spans=("train.batch", "train.step"))


def test_window_and_busy(summary):
    tr = T.load(str(DATA))
    lo, hi = T.window_bounds(tr)
    assert summary["n_devices"] == 1
    assert summary["window_s"] == pytest.approx((hi - lo) / 1e9)
    # one TensorCore runs one op at a time: busy is their summed time
    ops = sum(s for s, _ in summary["ops"].values())
    assert summary["busy_s"] == pytest.approx(ops, rel=1e-3)
    assert 0 < summary["busy_s"] < summary["window_s"]


def test_idle_gaps_named_by_host_span(summary):
    gaps = dict(summary["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6)
    batch = sum(v for k, v in gaps.items() if k.startswith("train.batch"))
    assert 0.06 <= batch <= 0.08          # three sleeps of 20 ms


def test_kernels_found_and_roofline_bounded(summary):
    names = [T.kernel_name(t) for t in summary["ops"]]
    assert names.count("group_min_scale") == 1
    assert names.count("group_max") == 1
    for text, (_, calls) in summary["ops"].items():
        if T.kernel_name(text).startswith("group_"):
            assert calls == 1
    share = load_module(METRICS / "group_reduce_roofline.py").read({
        "trace": summary,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}})
    assert 0 < share <= 100


def test_idle_share_reader(summary):
    share = load_module(METRICS / "idle_share.train.py").read(
        {"trace": summary})
    assert share == pytest.approx(
        100 * (1 - summary["busy_s"] / summary["window_s"]))
