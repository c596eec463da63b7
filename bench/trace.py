"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

A trace holds one plane per device (``/device:TPU:0`` ...) with an
``XLA Modules`` line (one event per program run on the device) and an
``XLA Ops`` line (one event per operation inside it), and a host plane
(``/host:CPU``) whose lines carry the harness's own
``jax.profiler.TraceAnnotation`` spans (``bench.window``, ``bench.fit``,
``bench.pump``, ``bench.await_arrival`` ...). The profiler keeps a
bounded number of operation events: a program that loops a million
times (a ``lax.scan`` over rows) loses most of them, while its module
event still spans the whole run. So:

* ``window_s``: the length of the ``bench.window`` span;
* ``busy_s``: the union of the module and operation intervals inside
  the window, averaged over the devices that ran anything;
* ``ops``: operation names by total device time inside the window, and
  for each module the busy time its recorded operations do not cover,
  under ``<module> [ops not recorded]``;
* ``gaps``: the idle stretches inside the window, each named by the
  innermost harness span around its midpoint;
* ``span_idle_s``: for each harness span name, the device-idle time
  inside the spans of that name (clipped to the window), averaged over
  the devices.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNRECORDED = " [ops not recorded]"
HOST_PLANE = "/host:CPU"
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    ops: List[Tuple[str, float]]  # (operation, seconds), longest first
    gaps: List[Tuple[str, float]]  # (host span, seconds), longest first
    span_idle_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax._src.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


# "%fusion.3 = f32[8,128]{...} fusion(...), kind=..." -> "%fusion.3 fusion"
_HLO = re.compile(r"^(%[\w.\-]+) = .*? ([\w\-]+)\(")


def op_name(text: str) -> str:
    """An operation's HLO instruction name and opcode, without its shapes
    and operands."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def _events(line):
    for ev in line.events:
        start = float(ev.start_ns)
        yield op_name(ev.name), start, start + float(ev.duration_ns)


def host_spans(profile) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans.extend(
                ev for ev in _events(line) if ev[0].startswith(SPAN_PREFIX)
            )
    return spans


def device_lines(profile) -> Dict[str, Dict[str, list]]:
    """{device plane: {"ops": events, "modules": events}}."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {"ops": [], "modules": []}
        for line in plane.lines:
            if line.name == OPS_LINE:
                lines["ops"] = list(_events(line))
            elif line.name == MODULES_LINE:
                lines["modules"] = list(_events(line))
        if lines["ops"] or lines["modules"]:
            out[plane.name] = lines
    return out


def _inside(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def _covered(merged: List[Interval], s: float, e: float) -> float:
    return sum(e2 - s2 for s2, e2 in clip(merged, s, e))


def _name_gap(spans, s: float, e: float) -> str:
    mid = 0.5 * (s + e)
    around = [sp for sp in spans if sp[1] <= mid <= sp[2]]
    inner = [sp for sp in around if sp[0] != WINDOW] or around
    if not inner:
        return "(no harness span)"
    return max(inner, key=lambda sp: sp[1])[0]


def summarize(profile, window: Optional[Interval] = None) -> Summary:
    """Reduce a loaded trace. ``window`` (ns) defaults to the
    ``bench.window`` span; it raises when the trace has neither."""
    spans = host_spans(profile)
    if window is None:
        marks = [sp for sp in spans if sp[0] == WINDOW]
        if not marks:
            raise ValueError(f"the trace has no {WINDOW} span")
        window = (marks[-1][1], marks[-1][2])
    lo, hi = window
    busy, op_time = [], {}
    gaps: List[Tuple[str, float]] = []
    by_name: Dict[str, List[Interval]] = {}
    for n, s, e in spans:
        if n != WINDOW:
            by_name.setdefault(n, []).append((s, e))
    by_name = {n: union(clip(ivs, lo, hi)) for n, ivs in by_name.items()}
    span_idle = {n: 0.0 for n in by_name}
    for lines in device_lines(profile).values():
        ops = _inside(lines["ops"], lo, hi)
        modules = _inside(lines["modules"], lo, hi)
        if not ops and not modules:
            continue
        op_union = union([(s, e) for _, s, e in ops])
        merged = union(op_union + [(s, e) for _, s, e in modules])
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
        for n, s, e in modules:
            missing = (e - s) - _covered(op_union, s, e)
            if missing > 0:
                key = n + UNRECORDED
                op_time[key] = op_time.get(key, 0.0) + missing
        for n, ivs in by_name.items():
            span_idle[n] += sum(e - s - _covered(merged, s, e) for s, e in ivs)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_name_gap(spans, s, e), (e - s) * 1e-9))
    if not busy:
        raise ValueError("no device operation ran inside the window")
    ops = sorted(((n, t * 1e-9) for n, t in op_time.items()),
                 key=lambda x: -x[1])[:TOP]
    gaps = sorted(gaps, key=lambda x: -x[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        devices=len(busy),
        ops=ops,
        gaps=gaps,
        span_idle_s={n: t / len(busy) * 1e-9 for n, t in span_idle.items()},
    )
