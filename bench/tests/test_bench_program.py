"""The program's spans and kernel in a trace (``program_trace.py``) and
the readers built on them, on hand-made traces and on two traces
recorded on a TPU v5e by ``record_trace.py``: ``small.xplane.pb`` from a
program whose spans were not yet annotations and whose kernel had no
name, and ``program.xplane.pb`` from one with both."""

import os
from types import SimpleNamespace as NS

import pytest

import harness
import program_trace

trace = harness.trace_lib
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
UNNAMED = os.path.join(DATA, "small.xplane.pb")
NAMED = os.path.join(DATA, "program.xplane.pb")
SPEC = harness.load_spec()


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _profile(device_events, host_events):
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[_line("XLA Ops", device_events)]),
        NS(name="/host:CPU", lines=[_line("main", host_events)]),
    ])


def _without_program_spans(profile):
    """A copy of a loaded trace without the program's host spans."""
    return NS(planes=[
        NS(name=p.name, lines=[
            NS(name=ln.name, events=[
                NS(name=e.name, start_ns=e.start_ns,
                   duration_ns=e.duration_ns)
                for e in ln.events
                if not e.name.startswith(program_trace.PROGRAM_PREFIXES)])
            for ln in p.lines])
        for p in profile.planes])


def test_innermost_names_each_piece_by_the_span_that_started_last():
    spans = [("engine.run", 0, 100), ("engine.epoch", 10, 60),
             ("engine.sync", 30, 50), ("serve.pump", 120, 130)]
    assert program_trace.innermost(spans) == [
        (0, 10, "engine.run"), (10, 30, "engine.epoch"),
        (30, 50, "engine.sync"), (50, 60, "engine.epoch"),
        (60, 100, "engine.run"), (120, 130, "serve.pump"),
    ]


def test_program_idle_by_hand():
    # window 0..200 ns; the device busy 20..40 and 70..150; an epoch
    # 10..160 holding a sync 40..80, and a loss pass 170..190
    profile = _profile(
        [("%igd_serial.1 = f32[1] custom-call()", 20, 20),
         ("%igd_serial.1 = f32[1] custom-call()", 70, 80)],
        [("bench.window", 0, 200), ("bench.fit", 0, 200),
         ("engine.epoch", 10, 150), ("engine.sync", 40, 40),
         ("engine.loss", 170, 20), ("jit_step", 5, 2)],
    )
    idle = program_trace.idle_by_span(profile)
    # device idle: 0..20, 40..70, 150..200
    assert idle == pytest.approx({
        "engine.epoch": 20e-9,  # 10..20 and 150..160
        "engine.sync": 30e-9,  # 40..70
        "engine.loss": 20e-9,  # 170..190
    })
    s = trace.summarize(profile)
    # idle outside every program span (0..10, 160..170, 190..200) is left out
    assert sum(idle.values()) == pytest.approx(
        s.window_s - s.busy_s - 30e-9)
    assert program_trace.kernel_s(s.ops) == pytest.approx(100e-9)


def test_the_kernel_is_found_by_its_name_only():
    ops = [("%igd_serial.1 custom-call", 2.0),
           ("%igd_serial.2 custom-call", 3.0),
           ("%igd_minibatch.1 custom-call", 0.5),
           ("%fusion fusion", 9.0)]
    assert program_trace.kernel_s(ops) == pytest.approx(5.5)
    # the unnamed kernel of a program before the name, alone and vmapped
    assert program_trace.kernel_s([
        ("%igd_fold.1 custom-call", 2.0),
        ("%vmap_jit_igd_fold__.2 custom-call", 3.0)]) is None


def _cell(metric):
    workload = next(m for m in SPEC["per_layer"]
                    if m["name"] == metric)["workloads"][0]
    return harness.resolve(workload, SPEC)


def _ctx(cell, ops, calls=(1, 2, 1)):
    summary = trace.Summary(window_s=10.0, busy_s=9.0, devices=1, ops=ops,
                            gaps=[])
    return harness.Context(cell, NS(calls=list(calls), window_info={}), {},
                           summary, harness.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("suffix", ["fit", "select"])
def test_kernel_roofline_by_hand(suffix):
    name = f"kernel_roofline.{suffix}"
    read = harness.load_reader(name)
    fold = harness.load_reader(f"fold_roofline.{suffix}")
    ctx = _ctx(_cell(name), [("%igd_serial.1 custom-call", 4.5),
                             ("%fusion fusion", 4.5)])
    # half the busy time is the kernel: twice fold_roofline's share
    assert read(ctx) == pytest.approx(2 * fold(ctx))
    assert 0 < read(ctx) < 100
    unnamed = _ctx(_cell(name), [("%igd_fold.1 custom-call", 4.5)])
    assert read(unnamed) is None


def test_compile_s_by_hand():
    from repro import obs

    read = harness.load_reader("compile_s")
    ctx = _ctx(_cell("compile_s"), [])
    saved = obs.metrics.REGISTRY._metrics
    obs.metrics.REGISTRY._metrics = {}
    try:
        assert read(ctx) is None  # a program without the histograms
        for name, seconds in (("jax.trace_s", 0.5), ("jax.lowering_s", 0.25),
                              ("jax.compile_s", 2.0),
                              ("jax.cache_load_s", 0.125)):
            obs.metrics.observe(name, seconds)
        obs.metrics.observe("jax.compile_s", 1.0)
        assert read(ctx) == pytest.approx(3.875)
        assert read(_ctx(_cell("compile_s"), [], calls=())) is None
    finally:
        obs.metrics.REGISTRY._metrics = saved


def test_the_trace_before_the_names_gives_the_new_readers_nothing():
    """The parent program's recorded trace: no program span, and the
    kernel unnamed, so kernel_roofline reads nothing there."""
    profile = trace.load(UNNAMED)
    assert program_trace.program_spans(profile) == []
    assert program_trace.idle_by_span(profile) == {}
    s = trace.summarize(profile)
    assert program_trace.kernel_s(s.ops) is None
    read = harness.load_reader("kernel_roofline.fit")
    assert read(_ctx(_cell("kernel_roofline.fit"), s.ops)) is None


def test_the_recorded_program_trace():
    profile = trace.load(NAMED)
    s = trace.summarize(profile)
    assert s.devices == 1 and 0 < s.busy_s < s.window_s
    # the kernel under its name, alone (the fit) and vmapped (the batch)
    kernels = [n for n, _ in s.ops if program_trace.KERNEL.match(n)]
    assert len(kernels) == 2, s.ops
    assert not any("igd_fold" in n for n, _ in s.ops)
    names = {n for n, _, _ in program_trace.program_spans(profile)}
    assert {"engine.run", "engine.epoch", "engine.order", "engine.sync",
            "engine.loss", "serve.pump", "serve.assemble",
            "serve.execute"} <= names
    idle = program_trace.idle_by_span(profile)
    assert set(idle) <= names
    assert idle["engine.epoch"] > 0
    assert sum(idle.values()) <= s.window_s - s.busy_s + 1e-9
    # the wait for an arrival holds no program span
    assert sum(idle.values()) < s.window_s - s.busy_s - 0.04


def test_program_spans_leave_the_harness_reduction_as_it_was():
    """Every field the existing readers use reads the same with the
    program's spans in the trace and without them."""
    profile = trace.load(NAMED)
    assert trace.summarize(profile) == \
        trace.summarize(_without_program_spans(profile))
