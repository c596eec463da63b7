"""probe_s: seconds the planner's probes took in set-up, the total of
the program's ``probes.calibrate_s`` histogram (a host wall around
blocking probes; its sum is exact)."""


def read(ctx):
    return ctx.setup.get("probe_s") or None
