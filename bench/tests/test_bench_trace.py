"""The trace reduction, on hand-made traces and on a small trace
recorded on a TPU v5e by ``record_trace.py``."""

import os
from types import SimpleNamespace as NS

import pytest

import harness

trace = harness.trace_lib
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _profile(device_lines, host_events):
    return NS(planes=[
        NS(name="/device:TPU:0",
           lines=[_line(n, ev) for n, ev in device_lines.items()]),
        NS(name="/host:CPU", lines=[_line("python", host_events)]),
    ])


def test_union_and_clip():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.clip([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]


def test_op_names_drop_shapes_and_operands():
    assert trace.op_name(
        "%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(f32[8] %a), kind=kLoop"
    ) == "%fusion.3 fusion"
    assert trace.op_name(
        "%while.14 = (s32[]{:T(128)}, f32[2,54]{1,0}) while((s32[]) %t)"
    ) == "%while.14 while"
    assert trace.op_name("jit_step(123)") == "jit_step(123)"


def test_busy_idle_and_gaps_by_hand():
    # window 0..100 ns; a module 10..50 whose ops cover 10..20 only
    # (the rest were not recorded), and an op alone at 70..80
    profile = _profile(
        {"XLA Modules": [("jit_fit(1)", 10, 40)],
         "XLA Ops": [("%k = f32[1] custom-call(f32[1] %a)", 10, 10),
                     ("%f = f32[1] fusion(f32[1] %b)", 70, 10)]},
        [("bench.window", 0, 100), ("bench.fit", 5, 50),
         ("bench.await_arrival", 55, 15)],
    )
    s = trace.summarize(profile)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(50e-9)
    assert s.idle_share == pytest.approx(0.5)
    assert dict(s.ops) == pytest.approx({
        "jit_fit(1)" + trace.UNRECORDED: 30e-9,
        "%k custom-call": 10e-9, "%f fusion": 10e-9})
    # gaps 0..10 (in bench.fit), 50..70 (await), 80..100 (window only)
    assert s.gaps == pytest.approx([("bench.await_arrival", 20e-9),
                                    ("bench.window", 20e-9),
                                    ("bench.fit", 10e-9)])
    # idle inside each span: bench.fit 5..55 holds 5..10 and 50..55
    assert s.span_idle_s == pytest.approx({"bench.fit": 10e-9,
                                           "bench.await_arrival": 15e-9})


def test_a_trace_without_device_work_is_refused():
    profile = _profile({}, [("bench.window", 0, 100)])
    with pytest.raises(ValueError):
        trace.summarize(profile)
    with pytest.raises(ValueError):
        trace.summarize(_profile({"XLA Ops": [("%a", 1, 2)]}, []))


def test_the_recorded_chip_trace():
    s = trace.summarize(trace.load(DATA))
    assert s.devices == 1
    assert 0.05 < s.window_s < 30
    assert 0 < s.busy_s < s.window_s
    names = [n for n, _ in s.ops]
    assert any("custom-call" in n for n in names), names  # the IGD kernel
    assert len(s.ops) <= trace.TOP and len(s.gaps) <= trace.TOP
    assert [t for _, t in s.gaps] == sorted((t for _, t in s.gaps),
                                            reverse=True)
    # the longest idle stretch is the recorder's 50 ms wait for an arrival
    assert s.gaps[0][0] == "bench.await_arrival"
    assert s.gaps[0][1] == pytest.approx(0.05, rel=0.5)
    assert {n for n, _ in s.gaps} <= {"bench.window", "bench.fit",
                                      "bench.pump", "bench.await_arrival"}
    idle = s.span_idle_s
    assert idle["bench.await_arrival"] == pytest.approx(0.05, rel=0.5)
    assert idle["bench.pump"] > 0
    assert sum(idle.values()) <= s.window_s - s.busy_s + 1e-9
