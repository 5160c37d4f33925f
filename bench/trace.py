"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` has one event per executed HLO instruction, named by the
instruction's text (``%group_min_scale.1 = f32[1,2048]... custom-call(...)``),
and a host plane (``/host:CPU``) with a line per thread; the main
thread's line carries the harness's ``jax.profiler.TraceAnnotation``
spans, among them ``bench.window``.  Both planes are on one clock.

* Busy time of a chip: the union of its ``XLA Ops`` intervals inside the
  window span; idle is the rest of the window.
* Per-op time: summed self time (an op's duration less the ops nested in
  it, as a loop's body ops are in the loop) by instruction, over chips.
* Collective time: the union of the collective instructions' intervals.
* Idle gaps: the gaps between busy intervals on the first chip, each
  named by the harness span and the outermost other host event that
  cover the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

Event = Tuple[float, float, str]          # (start_ns, duration_ns, name)

#: Shorter idle gaps are summed under one name instead of being labelled.
MIN_GAP_NS = 10_000

_NAME = re.compile(r"^%?([^\s=]+)")


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def latest_xplane(log_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under ``log_dir`` (``None`` if there is none)."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    """The device ops of every TPU plane and the events of the host thread
    that ran the window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            evs = tr.devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.start_ns, e.duration_ns, e.name)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            lines = [[(e.start_ns, e.duration_ns, e.name) for e in line.events]
                     for line in plane.lines]
            main = [ev for ev in lines if any(n == WINDOW_SPAN
                                              for _, _, n in ev)]
            tr.host = main[0] if main else []
    return tr


def op_name(text: str) -> str:
    """Instruction name of an ``XLA Ops`` event (``group_min_scale.1``)."""
    m = _NAME.match(text)
    return m.group(1) if m else text


def kernel_name(text: str) -> str:
    """The name without its numeric suffix (``group_min_scale``)."""
    return re.sub(r"\.\d+$", "", op_name(text))


def is_collective(text: str) -> bool:
    name = op_name(text)
    return any(name.startswith(c) for c in COLLECTIVE_OPS)


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less the time of the events nested inside it
    (a ``while`` or ``conditional`` op spans the ops of its body)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [float(events[i][1]) for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        s, d, _ = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack and s + d <= events[stack[-1]][0] + events[stack[-1]][1]:
            own[stack[-1]] -= d
        stack.append(i)
    return own


def window_bounds(tr: Trace, span: str = WINDOW_SPAN) -> Tuple[float, float]:
    """``(start_ns, end_ns)`` of the harness's window span; the device
    events' extent where the span is missing."""
    for s, d, n in tr.host:
        if n == span:
            return s, s + d
    evs = [e for v in tr.devices.values() for e in v]
    if not evs:
        raise ValueError("trace has neither a window span nor device ops")
    return min(s for s, _, _ in evs), max(s + d for s, d, _ in evs)


class _HostIndex:
    """Which host events cover an instant: the innermost harness span and
    the outermost other event."""

    def __init__(self, tr: Trace, spans: Sequence[str]):
        import numpy as np
        self.np = np
        ev = [e for e in tr.host if e[2] != WINDOW_SPAN]
        self.start = np.array([s for s, _, _ in ev], float)
        self.end = np.array([s + d for s, d, _ in ev], float)
        self.dur = np.array([d for _, d, _ in ev], float)
        self.name = [n for _, _, n in ev]
        self.harness = np.array([n in spans for n in self.name], bool)

    def label(self, t: float) -> str:
        np = self.np
        cover = (self.start <= t) & (self.end >= t)
        name = "outside spans"
        h = np.flatnonzero(cover & self.harness)
        if h.size:
            name = self.name[h[np.argmin(self.dur[h])]]
        o = np.flatnonzero(cover & ~self.harness)
        if o.size:
            name += ": " + self.name[o[np.argmax(self.dur[o])]]
        return name


def summarize(tr: Trace, spans: Sequence[str] = (),
              top: int = 10) -> dict:
    """Busy, per-op, collective and idle-gap numbers of the window.

    Returns a dict with ``window_s``; ``busy_s`` and ``collective_s``
    (both averaged over chips); ``ops`` (instruction
    text -> ``[self seconds, calls]``, summed over chips); ``device_ops`` and ``idle_gaps``
    (at most ``top`` ``[name, seconds]`` pairs each, largest first; gaps
    are summed by what the host was doing).
    """
    lo, hi = window_bounds(tr)
    if not tr.devices:
        raise ValueError("trace has no TPU device plane")
    n_dev = len(tr.devices)
    busy, coll = {}, []
    ops: Dict[str, List[float]] = {}
    by_name: Dict[str, float] = {}
    first_busy: List[Tuple[float, float]] = []
    for k, (plane, evs) in enumerate(sorted(tr.devices.items())):
        inside = [(s, d, n) for s, d, n in evs if s < hi and s + d > lo]
        merged = union([(s, s + d) for s, d, _ in inside], lo, hi)
        busy[plane] = sum(e - s for s, e in merged) / 1e9
        if k == 0:
            first_busy = merged
        cm = union([(s, s + d) for s, d, n in inside if is_collective(n)],
                   lo, hi)
        coll.append(sum(e - s for s, e in cm) / 1e9)
        for (_, _, n), own in zip(inside, self_times(inside)):
            o = ops.setdefault(n, [0.0, 0])
            o[0] += own / 1e9
            o[1] += 1
            short = op_name(n)
            by_name[short] = by_name.get(short, 0.0) + own / 1e9
    gaps: Dict[str, float] = {}
    index = _HostIndex(tr, spans)
    edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            lab = (index.label((s + e) / 2) if e - s >= MIN_GAP_NS
                   else f"gaps under {MIN_GAP_NS / 1e3:g} us")
            gaps[lab] = gaps.get(lab, 0.0) + (e - s) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy.values()) / n_dev,
        "collective_s": sum(coll) / n_dev,
        "ops": ops,
        "device_ops": [[n, v / n_dev] for n, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v] for n, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "n_devices": n_dev,
    }
