#!/usr/bin/env python3
"""The program's own spans in a ``jax.profiler`` trace.

    python bench/program_trace.py <trace.xplane.pb>

``repro.obs`` enters a ``TraceAnnotation`` for every span while a
profiler collects, so the host plane of a trace carries the program's
spans (``engine.epoch``, ``engine.sync``, ``serve.pump`` ...) on the
same clock as the device's operations. ``idle_by_span`` charges each
stretch in which the device ran nothing to the innermost program span
the host was in at that moment: the time the device waited for the host
inside ``engine.epoch`` but outside ``engine.order`` and
``engine.sync`` is the executor's own work per epoch. ``kernel_s``
totals the device time of the fused-IGD kernel, found by its name.

The harness's reduction (``trace.py``) reads only its own ``bench.*``
spans; this module reads the rest of the same trace.
"""

from __future__ import annotations

import heapq
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

trace_lib = harness.trace_lib

PROGRAM_PREFIXES = ("engine.", "serve.", "probe.", "program.", "shard.")
# the fused-IGD kernel's pallas_call names, alone or vmapped:
# "%igd_serial.1 custom-call", "%igd_serial.2 custom-call"
KERNEL = re.compile(r"^%igd_(serial|minibatch)(\.\d+)? ")

Interval = Tuple[float, float]


def program_spans(profile) -> List[Tuple[str, float, float]]:
    """(name, start ns, end ns) of every program span on the host plane."""
    spans = []
    for plane in profile.planes:
        if plane.name != trace_lib.HOST_PLANE:
            continue
        for line in plane.lines:
            spans.extend(ev for ev in trace_lib._events(line)
                         if ev[0].startswith(PROGRAM_PREFIXES))
    return spans


def innermost(spans: List[Tuple[str, float, float]]
              ) -> List[Tuple[float, float, str]]:
    """The timeline cut where any span starts or ends, each piece named
    by the span covering it that started last (the innermost on one
    thread), pieces no span covers left out."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(spans, key=lambda sp: sp[1])
    # a heap whose top is the live span that started last (the shorter
    # first on a tie); spans that ended are dropped once they surface
    live: List[Tuple[float, float, float, str]] = []
    pieces, i = [], 0
    for lo, hi in zip(edges, edges[1:]):
        while i < len(order) and order[i][1] <= lo:
            name, s, e = order[i]
            heapq.heappush(live, (-s, e - s, e, name))
            i += 1
        while live and live[0][2] <= lo:
            heapq.heappop(live)
        if live:
            pieces.append((lo, hi, live[0][3]))
    return pieces


def _overlap(a: List[Interval], b: List[Tuple[float, float, str]]
             ) -> Dict[str, float]:
    """Sum of |a ∩ piece| per piece name; both sorted and disjoint."""
    out: Dict[str, float] = {}
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out[b[j][2]] = out.get(b[j][2], 0.0) + (hi - lo)
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _window(profile, window: Optional[Interval]) -> Interval:
    if window is not None:
        return window
    marks = [sp for sp in trace_lib.host_spans(profile)
             if sp[0] == trace_lib.WINDOW]
    if not marks:
        raise ValueError(f"the trace has no {trace_lib.WINDOW} span")
    return marks[-1][1], marks[-1][2]


def idle_by_span(profile, window: Optional[Interval] = None
                 ) -> Dict[str, float]:
    """Device-idle seconds inside the window (default: the harness's
    ``bench.window`` span) by innermost program span, averaged over the
    devices that ran anything. Idle time outside every program span is
    left out."""
    lo, hi = _window(profile, window)
    pieces = [(max(s, lo), min(e, hi), n)
              for s, e, n in innermost(program_spans(profile))
              if min(e, hi) > max(s, lo)]
    totals: Dict[str, float] = {}
    devices = 0
    for lines in trace_lib.device_lines(profile).values():
        events = trace_lib._inside(lines["ops"] + lines["modules"], lo, hi)
        if not events:
            continue
        devices += 1
        busy = trace_lib.union([(s, e) for _, s, e in events])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for name, t in _overlap(idle, pieces).items():
            totals[name] = totals.get(name, 0.0) + t
    if not devices:
        raise ValueError("no device operation ran inside the window")
    return {n: t / devices * 1e-9 for n, t in totals.items()}


def kernel_s(ops: List[Tuple[str, float]]) -> Optional[float]:
    """Device seconds of the fused-IGD kernel among a summary's
    ``(operation, seconds)``; None when no operation carries its name."""
    times = [t for name, t in ops if KERNEL.match(name)]
    return sum(times) if times else None


def main(path: str) -> int:
    profile = trace_lib.load(path)
    summary = trace_lib.summarize(profile)
    idle = idle_by_span(profile)
    print(f"window {summary.window_s:.6f}s, busy {summary.busy_s:.6f}s, "
          f"idle {summary.window_s - summary.busy_s:.6f}s, of which in "
          f"program spans {sum(idle.values()):.6f}s")
    for name, t in sorted(idle.items(), key=lambda x: -x[1]):
        print(f"  {name} {t * 1e3:.3f} ms")
    print(f"kernel {kernel_s(summary.ops)}s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
