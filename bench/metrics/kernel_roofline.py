"""kernel_roofline.<suffix>: the roofline's least time for the fold work
the window completed, as a share (%) of the device time of the fused-IGD
kernel in the traced window.

The work and the peaks are ``fold_roofline``'s; the denominator is the
kernel's own device time, found by the name the program gives its
``pallas_call`` (``igd_serial``, ``igd_minibatch``: the singleton and
the vmapped kernel alike), not all device busy time. A program whose
kernel carries no such name gives nothing to read."""

import harness
import program_trace


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.driver.calls:
        return None
    kernel = program_trace.kernel_s(ctx.trace.ops)
    if not kernel:
        return None
    return 100.0 * harness.fold_least_s(ctx) / kernel
