"""Spans and trace counters of the planner and the train launcher, on
JAX's own sinks.

There is no buffer, exporter or switch here.  Every measurement goes to
the two sinks JAX already has:

* ``jax.monitoring``: a span ends with
  ``record_event_duration_secs("/pipette/span/<name>", seconds,
  request=<id>)``; a trace counter fires
  ``record_event("/pipette/trace/<name>", request=<id>)`` each time the
  program traces a jitted function, and an executable-cache hit fires
  ``record_event("/pipette/exe_hit/<name>", request=<id>)`` where it
  would have traced; a layout event fires
  ``record_event("/pipette/layout/<name>", request=<id>, **numbers)``
  where a function is lowered with a layout it chose from its input's
  shapes.  Register a listener
  (``jax.monitoring.register_event_duration_secs_listener``,
  ``register_event_listener``) to export them; listeners run on the
  caller's thread.
* the profiler: a span is also a ``jax.profiler.TraceAnnotation`` named
  ``<name>`` with a ``request`` stat, on the host plane of a
  ``jax.profiler.trace``, which shares its clock with the device planes.

Spans time themselves with ``time.perf_counter()`` and expose
``.seconds``.  The request id lives in a context variable that
:func:`request` sets (``Planner.plan`` does, once a plan); spans of one
plan share it, and the profiler's nesting gives each span's parent.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import time
from typing import Iterator

import jax

SPAN_EVENT = "/pipette/span/"
TRACE_EVENT = "/pipette/trace/"
EXE_HIT_EVENT = "/pipette/exe_hit/"
LAYOUT_EVENT = "/pipette/layout/"

_request = contextvars.ContextVar("pipette_request", default=0)
_next_request = itertools.count(1)


@contextlib.contextmanager
def request() -> Iterator[int]:
    """Give the spans inside a new process-unique request id."""
    token = _request.set(next(_next_request))
    try:
        yield _request.get()
    finally:
        _request.reset(token)


class span:
    """``with span(name, **attrs) as s:`` times its body into ``s.seconds``
    and reports it to the profiler and to ``jax.monitoring``."""

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._rid = _request.get()
        self._ann = jax.profiler.TraceAnnotation(
            self.name, request=self._rid, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        jax.monitoring.record_event_duration_secs(
            SPAN_EVENT + self.name, self.seconds, request=self._rid)


def count_trace(name: str) -> None:
    """Fire ``/pipette/trace/<name>``: call it where a function handed to
    ``jax.jit`` is lowered, which traces it once.  (Wrapping the jitted
    functions to fire from inside their traces made each plan of a
    2,048-GPU fleet trace for about 0.9 s longer on a TPU v5e.)"""
    jax.monitoring.record_event(TRACE_EVENT + name, request=_request.get())


def count_exe_hit(name: str) -> None:
    """Fire ``/pipette/exe_hit/<name>``: call it where a compiled
    executable is reused in place of tracing and lowering ``name`` again."""
    jax.monitoring.record_event(EXE_HIT_EVENT + name, request=_request.get())


def count_layout(name: str, **numbers: int) -> None:
    """Fire ``/pipette/layout/<name>`` with ``numbers`` as attributes: call
    it where a function is traced for lowering, with the layout it chose
    from its input's shapes, so it fires once a lowering and never per
    call of the compiled executable."""
    jax.monitoring.record_event(LAYOUT_EVENT + name, request=_request.get(),
                                **numbers)
