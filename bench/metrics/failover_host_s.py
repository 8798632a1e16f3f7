"""failover_host_s: host seconds from a fault change to the return of the
first step's call on the new program (plan, rebuild, trace, lower, compile
or cache load, dispatch), the mean over the window's switches."""


def read(ctx):
    sw = ctx["window"].switches
    if not sw:
        return None
    return sum(s["host_s"] for s in sw) / len(sw)
