"""call_idle_ms: milliseconds per device call of the serving front end
in which the device ran nothing while the server was serving: the idle
time inside the harness's ``bench.pump`` spans over the calls. It is the
host's share of a query's service time (planning, staging, dispatch,
sync); time spent waiting for arrivals is not in it, and a faster
kernel leaves it as it is."""


def read(ctx):
    calls = len(ctx.driver.calls)
    if ctx.trace is None or not calls:
        return None
    idle = ctx.trace.span_idle_s.get("bench.pump")
    if idle is None:
        return None
    return 1e3 * idle / calls
