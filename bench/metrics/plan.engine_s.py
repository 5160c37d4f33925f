"""Seconds per plan of the SA driver's engine construction, the pair-matrix
upload included (the program's ``sa.engine`` spans)."""

EVENT = "/pipette/span/sa.engine"


def read(run):
    d = [d for _, e, d in run["events"] if e == EVENT]
    return sum(d) / run["record"]["n"] if d else None
