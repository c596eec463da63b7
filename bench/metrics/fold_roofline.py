"""fold_roofline.<suffix>: the roofline's least time for the fold work
the window completed, as a share (%) of the device's busy time in the
traced window.

The work comes from the problem's shape (the configuration's
``epoch_work``), never from what a lowering does, and the peaks from
``peaks.json`` by ``device_kind``. The denominator is all device busy
time, so the share bounds every kernel's own from below."""

import harness


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.driver.calls:
        return None
    if ctx.trace.busy_s <= 0:
        return None
    return 100.0 * harness.fold_least_s(ctx) / ctx.trace.busy_s
