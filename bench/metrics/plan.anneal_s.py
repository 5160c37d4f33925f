"""Seconds per plan of the anneal: its device arguments, trace, compile or
cache load, dispatch and readback (the program's ``sa.anneal`` spans)."""

EVENT = "/pipette/span/sa.anneal"


def read(run):
    d = [d for _, e, d in run["events"] if e == EVENT]
    return sum(d) / run["record"]["n"] if d else None
