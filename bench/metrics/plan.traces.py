"""Traces per plan of the functions the JAX annealer hands to ``jax.jit``
(the program's ``/pipette/trace/*`` counters, which fire once a trace)."""

PREFIX = "/pipette/trace/"


def read(run):
    n = sum(1 for _, e, _ in run["events"] if e.startswith(PREFIX))
    if not n:
        return None
    q = n / run["record"]["n"]
    return int(q) if q.is_integer() else q
