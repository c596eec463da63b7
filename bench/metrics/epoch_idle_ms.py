"""epoch_idle_ms: milliseconds per epoch in which no operation ran on the
device over the traced window: (window - busy) / epochs. It is the host's
round trip between epoch programs (dispatch, sync, the next epoch's
set-up), and a faster kernel leaves it as it is."""


def read(ctx):
    info = ctx.driver.window_info
    if ctx.trace is None or not info.get("epochs"):
        return None
    return 1e3 * (ctx.trace.window_s - ctx.trace.busy_s) / info["epochs"]
