"""compile_s: seconds the program spent tracing, lowering, compiling and
loading compiled programs from the persistent cache, from its ``jax.*``
histograms (one ``jax.monitoring`` listener in ``repro.obs``; exact
sums, no second counted twice). Read after the window, so it holds
set-up and the window; a sound run compiles nothing in the window (the
harness counts the compiles there on stderr), so it is set-up's. A
program without the histograms gives nothing to read."""

HISTOGRAMS = ("jax.trace_s", "jax.lowering_s", "jax.compile_s",
              "jax.cache_load_s")


def read(ctx):
    if not ctx.driver.calls:
        return None
    from repro import obs

    snap = obs.metrics.snapshot("jax.")
    seconds = [snap[h]["sum"] for h in HISTOGRAMS if h in snap]
    return sum(seconds) if seconds else None
