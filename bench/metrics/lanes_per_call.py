"""lanes_per_call: queries served per device call of the serving front
end in the window (fused batches and singleton runs each count one
call), from ``ServingEngine.stats``. Exact counts."""


def read(ctx):
    info = ctx.driver.window_info
    calls = info.get("device_calls")
    if not calls:
        return None
    return info["served"] / calls
