"""repro.obs — the unified tracing/metrics layer.

The paper's thesis — one architecture so performance can be studied
generically — needs one *measurement* layer to match: before this
module, every execution driver (executor, serving front-end, sharded
driver) carried its own private ``time.perf_counter()`` arithmetic and
nothing could answer "where did this query's time go" across layers,
let alone "did the planner's calibrated cost model predict the run it
chose". Three pieces:

* **Span tracer** (``obs.span("engine.compile")``,
  ``obs.span("engine.epoch", index=i)``) — a process-global recorder
  with JSONL and Chrome-trace export. Every span is also a
  ``jax.profiler.TraceAnnotation`` while a profiler collects, so host
  spans share the device trace's clock. Disabled (the default) it
  records nothing: a few global checks returning a shared null context
  manager (or, under a profiler, the annotation alone), guarded by an
  overhead bench row. See :mod:`repro.obs.trace`.
* **Metrics registry** (``obs.metrics``) — counters, gauges, and
  fixed-log-bucket latency histograms with p50/p99. Always on; absorbs
  the timers the drivers used to keep privately (epoch/compile/loss
  walls, serve admission/queue-wait/assembly/execute breakdown, shard
  block walls) plus process-wide sources registered below (the
  ``tracecount`` retrace tally, and JAX's own trace / lowering /
  compile / cache-load events as ``jax.*`` counters and histograms).
  See :mod:`repro.obs.metrics`.
* **Drift detection** (``engine.explain_analyze(query)``) — run the
  chosen plan under the tracer and emit predicted-vs-measured cost per
  composed EpochProgram axis with drift ratios, persisted next to the
  plan in ``PlanStore``. See :mod:`repro.obs.drift`.

On top of those sits the **operational tier** — telemetry as an
always-on service rather than a before-the-run decision:

* **Exposition** (:mod:`repro.obs.export`) — the registry rendered as
  Prometheus text format and as a JSON snapshot, served by the stdlib
  HTTP thread in :mod:`repro.launch.obs_server` (``/metrics``,
  ``/snapshot``, ``/healthz``).
* **Flight recorder** (:mod:`repro.obs.flight`) — a bounded span ring
  cheap enough to leave on while full tracing is off, so the last N
  spans are always dumpable post-hoc.
* **SLO monitors** (:mod:`repro.obs.slo`) — declarative rules over the
  registry (p99 latency, shed rate, queue depth, stale calibration)
  evaluated on a cadence by ``ServingEngine.pump``; a breach dumps the
  flight ring into a JSONL incident file.
* **Tail-latency attribution** (:mod:`repro.obs.attribution`) —
  critical-path phase shares (queue-wait/assemble/compile/execute/
  merge) embedded in EXPLAIN ANALYZE reports and ``/snapshot``.

Typical use::

    from repro import obs

    with obs.tracing() as rec:
        engine.run(query)
    rec.export_jsonl("trace.jsonl")
    print(obs.metrics.snapshot("engine."))
"""

import threading
from typing import Tuple

from repro.obs import (  # noqa: F401
    attribution,
    drift,
    export,
    flight,
    metrics,
    slo,
    trace,
)
from repro.obs.attribution import PhaseReport  # noqa: F401
from repro.obs.drift import AxisCost, DriftReport  # noqa: F401
from repro.obs.flight import FlightRecorder  # noqa: F401
from repro.obs.slo import SLOMonitor, SLORule  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    NULL_SPAN,
    Recorder,
    disable,
    enable,
    enabled,
    get_recorder,
    span,
    tracing,
)


# JAX's duration events, each counted and timed as one of four kinds:
# (counter, histogram of seconds) per kind. A persistent-cache hit
# records its retrieval inside the backend-compile event of the same
# executable, so the listener books that backend event as a cache load
# instead of a compile: the four histograms never count one second
# twice.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_METRICS = (
    ("jax.traces", "jax.trace_s"),
    ("jax.lowerings", "jax.lowering_s"),
    ("jax.compiles", "jax.compile_s"),
    ("jax.cache_loads", "jax.cache_load_s"),
)
_KIND = {_TRACE: 0, _LOWER: 1}
# process-wide event counts in COMPILE_METRICS order; unlike the registry
# they survive reset_metrics, so a delta across any stretch is exact
_COMPILE_COUNTS = [0, 0, 0, 0]
_COUNTS_LOCK = threading.Lock()
_LISTENING = False
_HIT = threading.local()  # a cache retrieval awaits its backend event


def compile_counts() -> Tuple[int, int, int, int]:
    """(traces, lowerings, compiles, cache loads) so far in this process."""
    return tuple(_COMPILE_COUNTS)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _CACHE_HIT:
        _HIT.pending = True
        return
    if event == _BACKEND:
        kind = 3 if getattr(_HIT, "pending", False) else 2
        _HIT.pending = False
    else:
        kind = _KIND.get(event)
        if kind is None:
            return
    count, seconds = COMPILE_METRICS[kind]
    with _COUNTS_LOCK:
        _COMPILE_COUNTS[kind] += 1
        metrics.inc(count)
        metrics.observe(seconds, duration)


def _install_sources() -> None:
    """Register the process-wide sources (re-run after a registry reset):
    the shared retrace tally is a callback gauge like any other, so
    dashboards see recompiles next to latencies, and JAX's compile
    events feed the ``jax.*`` counters through one listener registered
    once per process."""
    global _LISTENING
    import jax

    from repro.core import tracecount

    metrics.gauge("core.retraces", fn=tracecount.global_traces)
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True


def reset_metrics() -> None:
    """Clear every metric, then re-register the built-in sources. The
    test fixtures use this so aggregates cannot leak between tests."""
    metrics.REGISTRY.reset()
    _install_sources()


def reset_operational() -> None:
    """Tear down the operational tier's process-global state (the test
    fixtures' other half): tracer off, flight ring uninstalled, recent
    SLO breaches cleared, and the obs HTTP server stopped if its module
    was ever imported (checked via ``sys.modules`` so tests that never
    start a server don't pay the import)."""
    import sys

    disable()
    flight.disable()
    slo.clear_breaches()
    server_mod = sys.modules.get("repro.launch.obs_server")
    if server_mod is not None:
        server_mod.stop()


_install_sources()
