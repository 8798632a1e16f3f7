"""plan_ms: the planner's own clock (Plan.gen_seconds) x 1e3, the mean
over the window's switches to a degraded state."""


def read(ctx):
    plans = [s["plan_s"] for s in ctx["window"].switches
             if s["plan_s"] is not None]
    if not plans:
        return None
    return 1e3 * sum(plans) / len(plans)
