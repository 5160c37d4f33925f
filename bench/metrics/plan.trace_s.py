"""Seconds per plan in which JAX traces a function to a jaxpr or lowers a
jaxpr to MLIR: the union of the intervals of its own duration events,
which nest (a trace holds the traces of the functions it calls), so their
plain sum would count time twice."""

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")


def read(run):
    spans = sorted((t - d, t) for t, e, d in run["events"] if e in EVENTS)
    if not spans:
        return None
    total, lo, hi = 0.0, *spans[0]
    for s, e in spans[1:]:
        if s > hi:
            total, lo = total + hi - lo, s
        hi = max(hi, e)
    return (total + hi - lo) / run["record"]["n"]
