"""Per-plan mean of the search pipeline's host phases before annealing
(``Overhead``: enumerate, memory estimate, profile, pre-score)."""


def read(run):
    ov = run["record"]["overheads"]
    return sum(o.enumerate_s + o.mem_estimator_s + o.profile_s + o.prescore_s
               for o in ov) / len(ov)
