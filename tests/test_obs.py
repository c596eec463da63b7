"""repro.obs: span tracer (enable/disable/nesting/export + schema),
metrics registry (counters/gauges/histograms + quantiles), tracecount
isolation, and EXPLAIN ANALYZE drift reports with PlanStore
persistence."""

import json
import math

import jax
import jax.numpy as jnp
import pytest

from repro import engine, obs
from repro.core import tracecount
from repro.data import synthetic
from repro.engine import serve
from repro.obs import drift, flight, metrics, trace

RNG = jax.random.PRNGKey(0)


def _q(data, **kw):
    kw.setdefault("epochs", 2)
    kw.setdefault("tolerance", 0.0)
    return engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 4}, **kw
    )


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_spans_nest_and_carry_attrs():
    with obs.tracing() as rec:
        with obs.span("outer", layer="test"):
            with obs.span("inner") as s:
                s.set(extra=1)
    assert len(rec) == 2
    inner, outer = rec.spans  # completion order: inner closes first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent"] == outer["id"]
    assert outer["parent"] is None
    assert outer["attrs"] == {"layer": "test"}
    assert inner["attrs"] == {"extra": 1}
    assert inner["dur"] >= 0 and inner["ts"] >= outer["ts"]


def test_tracing_restores_prior_state():
    assert not obs.enabled()
    with obs.tracing() as outer_rec:
        with obs.tracing() as inner_rec:
            assert obs.get_recorder() is inner_rec
        # back on the outer recorder, still enabled
        assert obs.enabled() and obs.get_recorder() is outer_rec
        with obs.span("after-inner"):
            pass
        assert len(outer_rec) == 1 and len(inner_rec) == 0
    assert not obs.enabled()


def test_disabled_path_records_zero_spans():
    """The no-op pin: with tracing off, span() returns the shared null
    context manager and no recorder gains anything — including from a
    real engine run, which is instrumented throughout."""
    rec = obs.enable()
    obs.disable()
    before = len(rec)
    with obs.span("not-recorded", attr=1):
        pass
    data = synthetic.dense_classification(RNG, 64, 4)
    engine.Engine().run(_q(data))
    assert len(rec) == before
    assert obs.span("x") is trace.NULL_SPAN


def test_disabled_span_cost_measures_off_path_only():
    cost = trace.disabled_span_cost(iters=2000)
    assert 0 < cost < 1e-4  # a global check + a kwargs dict, not more
    with obs.tracing():
        with pytest.raises(RuntimeError):
            trace.disabled_span_cost(iters=10)


def _profiled_host_events(log_dir, body):
    """Run ``body`` under a CPU ``jax.profiler`` trace; the host plane's
    (name, start ns, end ns) events."""
    import glob
    import os

    from jax._src.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                )
    return events


@pytest.mark.parametrize("mode", ["tracing", "flight", "off"])
def test_spans_are_profiler_annotations_in_every_mode(tmp_path, mode):
    """Recorded, in the flight ring only, or neither: a span shows up in
    a profiler trace under its name, nested as it ran; ``set`` works;
    and with both off no recorder gains anything."""
    ring = flight.enable(capacity=8) if mode == "flight" else None
    rec = obs.enable(trace.Recorder())
    if mode != "tracing":
        obs.disable()  # the recorder stays installed, and must stay empty

    def body():
        with obs.span("test.outer", layer="test") as outer:
            outer.set(extra=1)
            with obs.span("test.inner") as inner:
                assert inner.set(k=2) is inner
                jnp.zeros(4).block_until_ready()

    events = _profiled_host_events(tmp_path, body)
    obs.disable()
    (outer,) = [e for e in events if e[0] == "test.outer"]
    (inner,) = [e for e in events if e[0] == "test.inner"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    if mode == "tracing":
        assert [s["name"] for s in rec.spans] == ["test.inner", "test.outer"]
        assert rec.spans[1]["attrs"] == {"layer": "test", "extra": 1}
    elif mode == "flight":
        assert [s["name"] for s in ring.snapshot_spans()] == \
            ["test.inner", "test.outer"]
        assert len(rec) == 0
    else:
        assert len(rec) == 0 and flight.get() is None
    # outside a profiler trace the fully-off path is the shared null span
    if mode == "off":
        assert obs.span("x") is trace.NULL_SPAN


def test_compile_listener_counts_traces_and_compiles():
    """One jax.monitoring listener feeds the jax.* counters; it was
    registered once and keeps counting after the registry is reset (the
    autouse fixture resets it between tests)."""
    for _ in range(2):
        before = obs.compile_counts()
        jax.jit(lambda x: x * 7 + 1)(jnp.ones(5)).block_until_ready()
        after = obs.compile_counts()
        traces, lowerings, compiles, loads = (
            b - a for a, b in zip(before, after)
        )
        assert traces >= 1 and lowerings >= 1 and compiles >= 1
        assert loads == 0  # the tests run without the persistent cache
        snap = obs.metrics.snapshot("jax.")
        assert snap["jax.compiles"]["value"] >= compiles
        assert snap["jax.compile_s"]["count"] == snap["jax.compiles"]["value"]
        assert snap["jax.trace_s"]["sum"] > 0
        obs.reset_metrics()
        assert obs.metrics.snapshot("jax.") == {}


def test_a_persistent_cache_hit_counts_as_a_load():
    """The backend-compile event that follows a cache retrieval is booked
    as a cache load, so no second lands in two histograms."""
    from repro import obs as obs_pkg

    obs_pkg._on_duration(obs_pkg._CACHE_HIT, 0.01)
    obs_pkg._on_duration(obs_pkg._BACKEND, 0.25)
    obs_pkg._on_duration(obs_pkg._BACKEND, 2.0)
    snap = obs.metrics.snapshot("jax.")
    assert snap["jax.cache_loads"]["value"] == 1
    assert snap["jax.cache_load_s"]["sum"] == 0.25
    assert snap["jax.compiles"]["value"] == 1
    assert snap["jax.compile_s"]["sum"] == 2.0


def test_jsonl_export_validates_and_chrome_trace_loads(tmp_path):
    with obs.tracing() as rec:
        with obs.span("a", task="logreg"):
            with obs.span("b"):
                pass
    jsonl = tmp_path / "trace.jsonl"
    chrome = tmp_path / "trace.json"
    assert rec.export_jsonl(str(jsonl)) == 2
    assert trace.validate_jsonl(str(jsonl)) == 2
    assert rec.export_chrome_trace(str(chrome)) == 2
    events = json.loads(chrome.read_text())["traceEvents"]
    assert {e["ph"] for e in events} == {"X"}
    assert {e["name"] for e in events} == {"a", "b"}


def test_validate_jsonl_rejects_bad_lines(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"name": "x", "id": 0}\n')
    with pytest.raises(ValueError, match="missing"):
        trace.validate_jsonl(str(bad))
    bad.write_text(
        '{"name": "x", "id": 0, "parent": null, "ts": -1.0, "dur": 0.0, '
        '"tid": 1, "attrs": {}}\n'
    )
    with pytest.raises(ValueError, match="negative"):
        trace.validate_jsonl(str(bad))


def test_recorder_find_and_total():
    with obs.tracing() as rec:
        for _ in range(3):
            with obs.span("loop"):
                pass
    assert len(rec.find("loop")) == 3
    assert rec.total("loop") == pytest.approx(
        sum(s["dur"] for s in rec.spans)
    )
    assert rec.find("missing") == [] and rec.total("missing") == 0.0


# ---------------------------------------------------------------------------
# tracecount isolation
# ---------------------------------------------------------------------------


def test_tracecount_snapshot_restore():
    before = tracecount.snapshot()
    fn = tracecount.counted_jit(lambda x: x + 1)
    fn(jnp.zeros(2))
    assert tracecount.global_traces() == before + 1
    tracecount.restore(before)
    assert tracecount.global_traces() == before


def test_tracecount_isolation_fixture_part_one():
    """Bumps the process-wide tally; the autouse fixture must restore it
    before the companion test below runs (pytest executes them in file
    order within one process)."""
    global _TALLY_SEEN
    _TALLY_SEEN = tracecount.snapshot()
    fn = tracecount.counted_jit(lambda x: x * 2)
    fn(jnp.zeros(3))
    assert tracecount.global_traces() == _TALLY_SEEN + 1


def test_tracecount_isolation_fixture_part_two():
    assert tracecount.global_traces() == _TALLY_SEEN


def test_retraces_surface_as_metric():
    before = tracecount.global_traces()
    fn = tracecount.counted_jit(lambda x: x - 1)
    fn(jnp.zeros(2))
    snap = obs.metrics.snapshot("core.")
    assert snap["core.retraces"]["value"] == before + 1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_and_callback_gauge():
    obs.metrics.inc("t.count")
    obs.metrics.inc("t.count", 4)
    obs.metrics.set_gauge("t.gauge", 7)
    obs.metrics.gauge("t.live", fn=lambda: 42)
    snap = obs.metrics.snapshot("t.")
    assert snap["t.count"] == {"type": "counter", "value": 5}
    assert snap["t.gauge"]["value"] == 7
    assert snap["t.live"]["value"] == 42  # callback read at snapshot time


def test_metric_type_conflicts_raise():
    obs.metrics.inc("t.name")
    with pytest.raises(TypeError, match="Counter"):
        obs.metrics.observe("t.name", 1.0)


def test_histogram_quantiles_and_stats():
    h = metrics.Histogram()
    for v in [1e-3] * 98 + [0.5, 1.0]:
        h.observe(v)
    assert h.count == 100
    assert h.mean == pytest.approx((0.098 + 1.5) / 100)
    assert h.vmin == 1e-3 and h.vmax == 1.0
    # p50 sits in the 1ms bucket; p99 reaches the outlier tail
    assert h.p50 == pytest.approx(1e-3, rel=0.8)
    assert h.p99 >= 0.5
    assert h.quantile(1.0) == 1.0
    empty = metrics.Histogram()
    assert empty.p50 == 0.0 and empty.mean == 0.0
    single = metrics.Histogram()
    single.observe(3e-4)
    # clamped to the observed sample, not a bucket edge
    assert single.p50 == 3e-4 and single.p99 == 3e-4


def test_reset_metrics_reinstalls_builtin_sources():
    obs.metrics.inc("t.junk")
    obs.reset_metrics()
    assert obs.metrics.snapshot("t.") == {}
    assert "core.retraces" in obs.metrics.snapshot("core.")


def test_engine_run_feeds_epoch_histograms():
    data = synthetic.dense_classification(RNG, 64, 4)
    engine.Engine().run(_q(data, epochs=3))
    snap = obs.metrics.snapshot("engine.")
    assert snap["engine.epoch.grad_s"]["count"] == 3
    assert snap["engine.epoch.shuffle_s"]["count"] == 3
    assert snap["engine.compile_s"]["count"] >= 1
    assert snap["engine.loss_s"]["count"] == 1


# ---------------------------------------------------------------------------
# drift reports / EXPLAIN ANALYZE
# ---------------------------------------------------------------------------


def test_drift_ratio_noise_handling():
    assert drift.drift_ratio(0.0, 0.0) == 1.0
    assert drift.drift_ratio(0.0, 1e-6) == 1.0  # dispatch noise, not drift
    assert math.isinf(drift.drift_ratio(0.0, 0.5))
    assert drift.drift_ratio(0.1, 0.2) == pytest.approx(2.0)


def test_drift_report_describe_and_staleness():
    rows = (
        obs.AxisCost("ordering", 0.010, 0.012, "walls"),
        obs.AxisCost("parallelism", 0.100, 0.110, "walls"),
    )
    rep = obs.DriftReport(
        axes="ordering=clustered", plan={}, rows=rows, epochs_run=2,
        predicted_total_s=0.110, measured_total_s=0.122,
    )
    assert not rep.stale and rep.drift == pytest.approx(0.122 / 0.110)
    text = rep.describe()
    assert "EXPLAIN ANALYZE" in text and "calibration: ok" in text
    bad = obs.DriftReport(
        axes="x", plan={}, rows=rows, epochs_run=2,
        predicted_total_s=0.010, measured_total_s=0.200,
    )
    assert bad.stale and "STALE" in bad.describe()


def test_drift_report_round_trips_through_json():
    rows = (obs.AxisCost("source", 0.0, 0.0, "materialize"),)
    rep = obs.DriftReport(
        axes="a", plan={"ordering": "clustered"}, rows=rows, epochs_run=1,
        predicted_total_s=0.0, measured_total_s=0.0,
    )
    back = obs.DriftReport.from_dict(json.loads(json.dumps(rep.to_dict())))
    assert back == rep


def test_explain_analyze_reports_per_axis_drift():
    data = synthetic.dense_classification(RNG, 256, 4)
    eng = engine.Engine()
    rep = eng.explain_analyze(_q(data, epochs=3))
    assert [r.axis for r in rep.rows] == [
        "ordering", "parallelism", "batching", "source", "implementation",
    ]
    assert rep.epochs_run == 3
    assert rep.measured_total_s > 0 and rep.predicted_total_s > 0
    assert rep.predicted_total_s == pytest.approx(
        sum(r.predicted_s for r in rep.rows)
    )
    assert all(r.ratio > 0 for r in rep.rows)
    assert "EXPLAIN ANALYZE" in rep.describe()
    # the analyzed run restored the caller's tracer state
    assert not obs.enabled()


def test_explain_analyze_persists_next_to_plan(tmp_path):
    data = synthetic.dense_classification(RNG, 128, 4)
    store = serve.PlanStore(str(tmp_path))
    rep = engine.Engine(plan_store=store).explain_analyze(_q(data))
    # a fresh engine (fresh process stand-in) reads the measured run back
    fresh = engine.Engine(plan_store=store)
    loaded = fresh.load_analysis(_q(data))
    assert loaded is not None
    assert loaded.measured_total_s == pytest.approx(rep.measured_total_s)
    assert loaded.epochs_run == rep.epochs_run
    assert [r.axis for r in loaded.rows] == [r.axis for r in rep.rows]
    # the analysis file sits NEXT TO the plan entry, not inside it
    names = sorted(p.name for p in tmp_path.iterdir())
    assert any(n.endswith(".analyze.json") for n in names)
    assert any(
        n.endswith(".json") and ".analyze" not in n for n in names
    )
    # a different table (different fingerprint) must read as a miss
    other = synthetic.dense_classification(jax.random.PRNGKey(9), 128, 4)
    assert fresh.load_analysis(_q(other)) is None
