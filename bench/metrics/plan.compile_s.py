"""Seconds per plan of XLA compiles and persistent-cache loads inside the
window, from JAX's own ``backend_compile_duration`` events."""

EVENT = "/jax/core/compile/backend_compile_duration"


def read(run):
    return sum(d for _, e, d in run["events"] if e == EVENT) \
        / run["record"]["n"]
