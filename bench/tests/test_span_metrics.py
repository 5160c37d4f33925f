"""The readers of the program's spans and trace counters, and of JAX's
trace events, on synthetic runs: ``run["events"]`` holds
``(perf_counter at the event's end, event, seconds)`` as the harness
records them."""
from pathlib import Path

import pytest

from bench.run import load_module

METRICS = Path(__file__).resolve().parents[1] / "metrics"
SPAN = "/pipette/span/"
TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"
MLIR_EV = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def read(metric, events, n=2):
    return load_module(METRICS / f"{metric}.py").read(
        {"events": events, "record": {"n": n}})


@pytest.mark.parametrize("metric, span", [("plan.engine_s", "sa.engine"),
                                          ("plan.coarse_s", "sa.coarse"),
                                          ("plan.anneal_s", "sa.anneal")])
def test_span_readers_sum_their_span_per_plan(metric, span):
    events = [(10.0, SPAN + span, 0.5), (11.0, SPAN + span, 0.25),
              (12.0, SPAN + "sa.prepare", 4.0), (13.0, SPAN + span, 0.75),
              (14.0, TRACE_EV, 9.0)]
    assert read(metric, events) == pytest.approx(0.75)
    assert read(metric, [e for e in events if e[1] != SPAN + span]) is None


def test_traces_counts_per_plan():
    events = [(1.0 + k, "/pipette/trace/jax_engine." + name, 0.0)
              for k, name in enumerate(("score", "anneal") * 4)]
    events.append((20.0, SPAN + "sa.engine", 1.0))
    got = read("plan.traces", events)
    assert got == 4 and isinstance(got, int)
    assert read("plan.traces", events[:3]) == 1.5
    assert read("plan.traces", events[-1:]) is None


def test_trace_s_is_the_union_of_nested_events():
    events = [
        (2.0, TRACE_EV, 0.5),        # [1.5, 2.0] inside the next
        (3.0, TRACE_EV, 2.0),        # [1.0, 3.0]
        (2.5, MLIR_EV, 1.0),         # [1.5, 2.5] inside it too
        (3.5, MLIR_EV, 1.0),         # [2.5, 3.5] overlaps its end
        (6.0, TRACE_EV, 1.0),        # [5.0, 6.0] apart
        (9.0, SPAN + "sa.anneal", 8.0),
    ]
    assert read("plan.trace_s", events) == pytest.approx((2.5 + 1.0) / 2)
    assert sum(d for _, e, d in events if e != SPAN + "sa.anneal") == 5.5
    assert read("plan.trace_s", events[-1:]) is None
