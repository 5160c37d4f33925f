"""Seconds per plan of the SA driver's coarse island assignment, the first
``score`` trace and compile of each group included (the program's
``sa.coarse`` spans)."""

EVENT = "/pipette/span/sa.coarse"


def read(run):
    d = [d for _, e, d in run["events"] if e == EVENT]
    return sum(d) / run["record"]["n"] if d else None
