"""The reader of the program's executable-cache hit counters,
``plan.exe_hits``, on synthetic runs: ``run["events"]`` holds
``(perf_counter at the event's end, event, seconds)`` as the harness
records them."""
from pathlib import Path

from bench.run import load_module

METRICS = Path(__file__).resolve().parents[1] / "metrics"
SPAN = "/pipette/span/"
TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"


def read(events, n=2):
    return load_module(METRICS / "plan.exe_hits.py").read(
        {"events": events, "record": {"n": n}})


HITS = [(1.0 + k, "/pipette/exe_hit/jax_engine." + name, 0.0)
        for k, name in enumerate(("score", "anneal") * 4)]
TRACES = [(t, e.replace("exe_hit", "trace"), d) for t, e, d in HITS]
ANNEAL = [(20.0, SPAN + "sa.anneal", 1.0)]


def test_exe_hits_read_zero_where_the_program_only_traces():
    assert read(TRACES + ANNEAL) == 0


def test_exe_hits_read_hits_per_plan():
    got = read(HITS + ANNEAL)
    assert got == 4 and isinstance(got, int)
    assert read(HITS[:3] + ANNEAL) == 1.5


def test_exe_hits_read_none_without_hits_or_traces():
    assert read(ANNEAL + [(21.0, TRACE_EV, 2.0)]) is None
