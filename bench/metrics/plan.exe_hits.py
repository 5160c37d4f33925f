"""Executable-cache hits per plan: the program's ``/pipette/exe_hit/*``
counters, which fire where the JAX annealer reuses a compiled executable
in place of tracing its function again.  A program that traces but has
no such counter reads 0; a run with neither kind of event reads None."""

HIT = "/pipette/exe_hit/"
TRACE = "/pipette/trace/"


def read(run):
    names = [e for _, e, _ in run["events"]]
    if not any(e.startswith((HIT, TRACE)) for e in names):
        return None
    q = sum(e.startswith(HIT) for e in names) / run["record"]["n"]
    return int(q) if q.is_integer() else q
