"""How far the planner's latency model is from the step it chose: the
window's seconds a step against the plan's predicted seconds an
iteration, as a share of the prediction."""


def read(run):
    r = run["record"]
    if "plan_latency_s" not in r:
        return None
    step_s = r["window_s"] / r["steps"]
    return 100.0 * abs(step_s / r["plan_latency_s"] - 1.0)
