"""Analytics serving entry points: the engine's high-QPS front-end
(``repro.engine.serve``). ``make_analytics_server`` builds a
``ServingEngine`` (admission control + cross-query batching + optional
persistent plan cache) and ``serve_analytics`` runs a submit-and-drain
load, returning the tickets. ``benchmarks/serve_bench.py`` drives its
offered-load sweeps through these.
"""

from __future__ import annotations

import os
from typing import Iterable, List, Optional

from repro import obs
from repro.engine import serve as serve_lib


def make_analytics_server(
    *,
    cache_dir: Optional[str] = None,
    max_queue: int = 64,
    max_per_task: int = 32,
    max_batch: int = 8,
    slo_rules=None,
    incident_dir: Optional[str] = None,
) -> serve_lib.ServingEngine:
    """An analytics ``ServingEngine`` with the given admission knobs.
    ``slo_rules`` (a tuple of ``repro.obs.slo.SLORule``, e.g.
    ``slo.default_serve_rules()``) arms breach monitoring; incidents
    land in ``incident_dir`` (default: ``<cache_dir>/incidents``)."""
    return serve_lib.ServingEngine(
        serve_lib.ServeConfig(
            max_queue=max_queue,
            max_per_task=max_per_task,
            max_batch=max_batch,
            cache_dir=cache_dir,
            slo_rules=slo_rules,
            incident_dir=incident_dir,
        )
    )


def serve_analytics(
    queries: Iterable,
    *,
    server: Optional[serve_lib.ServingEngine] = None,
    trace_dir: Optional[str] = None,
    obs_port: Optional[int] = None,
    **server_kw,
) -> List[serve_lib.Ticket]:
    """Submit ``queries`` (admission-controlled), drain the queue, and
    return one ticket per query — rejected ones carry ``reject_reason``
    instead of a result. With ``trace_dir``, the whole load runs under
    the span tracer and ``serve.jsonl`` / ``serve.trace.json`` (Chrome
    trace) are written there after the drain. With ``obs_port`` (0 for
    an ephemeral port), the process obs server is started first, so
    ``/metrics``, ``/snapshot`` and ``/healthz`` are scrapeable while
    the load runs — and stay up afterwards
    (``repro.launch.obs_server.stop()`` tears it down)."""
    if obs_port is not None:
        from repro.launch import obs_server

        obs_server.start(obs_port)
    srv = server if server is not None else make_analytics_server(**server_kw)
    if trace_dir is None:
        tickets = [srv.submit(q) for q in queries]
        srv.drain()
        return tickets
    os.makedirs(trace_dir, exist_ok=True)
    with obs.tracing() as rec:
        tickets = [srv.submit(q) for q in queries]
        srv.drain()
    rec.export_jsonl(os.path.join(trace_dir, "serve.jsonl"))
    rec.export_chrome_trace(os.path.join(trace_dir, "serve.trace.json"))
    return tickets

