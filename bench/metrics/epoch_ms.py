"""epoch_ms: the mean of the program's ``engine.epoch.grad_s`` over the
window, in milliseconds: a host wall around each epoch program that ends
in ``block_until_ready``, with exact sum and count."""


def read(ctx):
    info = ctx.driver.window_info
    if not info.get("epochs"):
        return None
    return 1e3 * info["epoch_s"] / info["epochs"]
