"""Per-plan mean of the SA driver's time (``Overhead.sa_s``): engine
construction, pair-matrix upload, compiles, coarse assignment and the
anneal itself, which the program does not split yet."""


def read(run):
    ov = run["record"]["overheads"]
    return sum(o.sa_s for o in ov) / len(ov)
