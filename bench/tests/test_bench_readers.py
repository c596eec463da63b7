"""Each per-layer metric's reader, on a hand-made context: it reads its
number where there is something to read, and returns nothing where
there is not."""

from types import SimpleNamespace as NS

import pytest

import harness

SPEC = harness.load_spec()
NAMES = [m["name"] for m in SPEC["per_layer"]]


def _cell(metric):
    workload = next(m for m in SPEC["per_layer"]
                    if m["name"] == metric)["workloads"][0]
    return harness.resolve(workload, SPEC)


def _ctx(cell, *, trace=True, calls=(1, 2, 1, 1, 3)):
    summary = harness.trace_lib.Summary(
        window_s=10.0, busy_s=9.0, devices=1, ops=[], gaps=[],
        span_idle_s={"bench.pump": 0.4, "bench.await_arrival": 0.6},
    ) if trace else None
    driver = NS(calls=list(calls), window_info={
        "epochs": 100, "epoch_s": 7.0, "served": sum(calls),
        "device_calls": len(calls)} if calls else {})
    return harness.Context(cell, driver, {"probe_s": 2.0} if calls else {},
                           summary, harness.peaks_for("TPU v5 lite"))


def test_the_readers_by_hand():
    read = {n: harness.load_reader(n)(_ctx(_cell(n))) for n in NAMES}
    assert read["epoch_idle_ms"] == pytest.approx(1e3 * 1.0 / 100)
    assert read["call_idle_ms"] == pytest.approx(1e3 * 0.4 / 5)
    assert read["epoch_ms"] == pytest.approx(70.0)
    assert read["lanes_per_call"] == pytest.approx(8 / 5)
    assert read["probe_s"] == pytest.approx(2.0)
    for n in ("fold_roofline.fit", "fold_roofline.select"):
        assert 0 < read[n] < 100


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=NAMES)
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    reader = harness.load_reader(metric["name"])
    cell = _cell(metric["name"])
    assert reader(_ctx(cell, calls=())) is None
    if metric["source"] == "device_trace":
        assert reader(_ctx(cell, trace=False)) is None
