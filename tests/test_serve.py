"""repro.engine.serve: admission control, cross-query batching
equivalence, the persistent plan cache's warm start, and the executor's
MRS double-buffer swap."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import mrs as mrs_lib, uda as uda_lib
from repro.data import synthetic
from repro.engine import catalog, probes, serve

RNG = jax.random.PRNGKey(0)


def _q(data, seed=0, **kw):
    kw.setdefault("epochs", 2)
    kw.setdefault("tolerance", 0.0)
    return engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 4}, seed=seed, **kw
    )


# ---------------------------------------------------------------------------
# cross-query batching
# ---------------------------------------------------------------------------


def test_batched_results_match_serial():
    """A fused batch must return, per query, the same model/loss the
    singleton executor produces (same per-query rng streams + ordering).

    The physical plan is pinned by hints: under CPU contention the
    planner's micro-probe timings can legitimately pick a non-batchable
    plan (MRS), and this test is about fusion equivalence, not plan
    choice."""
    data = synthetic.dense_classification(RNG, 96, 4)
    hints = {"ordering": "shuffle_once", "scheme": "serial"}
    queries = [_q(data, seed=s, hints=hints) for s in (0, 1, 2)]
    eng = engine.Engine()
    serial = [eng.run(q) for q in queries]

    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    tickets = [srv.submit(q) for q in queries]
    assert srv.drain() == 3
    assert srv.stats["batches"] == 1
    assert srv.stats["batched_queries"] == 3
    assert srv.stats["fused_lanes"] == 3
    assert srv.metrics()["obs"]["serve.fused_lanes"]["value"] == 3
    for t, ref in zip(tickets, serial):
        assert t.done and t.result.batch_size == 3
        np.testing.assert_allclose(
            np.asarray(t.result.model), np.asarray(ref.model),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            t.result.losses[-1], ref.losses[-1], rtol=1e-5
        )


@pytest.mark.parametrize("hints", [
    # fused serial path with per-epoch in-run reshuffle
    {"ordering": "shuffle_always", "scheme": "serial"},
    # fixed path, shared table broadcast (ex_axis=None)
    {"ordering": "clustered", "scheme": "serial"},
    # fixed path through prep_fn + vmapped non-serial scheme
    {"ordering": "shuffle_once", "scheme": "segmented", "num_segments": 4},
])
def test_batched_matches_serial_across_plans(hints):
    """Every _batched_compile branch must preserve the singleton
    executor's results, not just the serial+shuffle_once headline."""
    data = synthetic.dense_classification(RNG, 96, 4)
    queries = [_q(data, seed=s, hints=hints) for s in (0, 1)]
    eng = engine.Engine()
    serial = [eng.run(q) for q in queries]
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    tickets = [srv.submit(q) for q in queries]
    srv.drain()
    assert srv.stats["batches"] == 1, hints
    for t, ref in zip(tickets, serial):
        np.testing.assert_allclose(
            np.asarray(t.result.model), np.asarray(ref.model),
            rtol=1e-5, atol=1e-7,
        )


def test_batched_matches_serial_with_distinct_tables():
    """Same-signature but different tables fuse on the stacked
    (non-broadcast) axes and must still match per-query serial runs."""
    d1 = synthetic.dense_classification(RNG, 96, 4)
    d2 = jax.tree.map(lambda x: x * 1.25, d1)
    hints = {"ordering": "shuffle_once", "scheme": "serial"}
    queries = [_q(d1, seed=0, hints=hints), _q(d2, seed=1, hints=hints)]
    eng = engine.Engine()
    serial = [eng.run(q) for q in queries]
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    tickets = [srv.submit(q) for q in queries]
    srv.drain()
    assert srv.stats["batches"] == 1
    for t, ref in zip(tickets, serial):
        np.testing.assert_allclose(
            np.asarray(t.result.model), np.asarray(ref.model),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            t.result.losses[-1], ref.losses[-1], rtol=1e-5
        )


def test_lmf_degrees_are_derived_from_the_table():
    """The documented lmf usage — no explicit degrees — must get the
    table-derived apportionment, not the over-penalizing 1.0 defaults."""
    rdata = synthetic.ratings(RNG, 32, 16, 512, rank=2)
    q = engine.AnalyticsQuery(
        task="lmf", data=rdata,
        task_args={"n_rows": 32, "n_cols": 16, "rank": 4, "mu": 1e-3},
        epochs=1, tolerance=0.0,
    )
    _, task, _ = engine.Engine()._aggregate_for(q)
    assert task.mean_row_degree == 512 / 32
    assert task.mean_col_degree == 512 / 16
    # explicit values always win over derivation
    q2 = engine.AnalyticsQuery(
        task="lmf", data=rdata,
        task_args={"n_rows": 32, "n_cols": 16, "rank": 4, "mu": 1e-3,
                   "mean_row_degree": 2.0},
        epochs=1, tolerance=0.0,
    )
    _, task2, _ = engine.Engine()._aggregate_for(q2)
    assert task2.mean_row_degree == 2.0 and task2.mean_col_degree == 1.0


def test_budgeted_queries_are_not_fused():
    """memory_budget_bytes bounds ONE query's footprint; stacking a
    fused batch would multiply it, so budgeted queries stay singleton."""
    data = synthetic.dense_classification(RNG, 96, 4)
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    budget = 10 * 1024 * 1024
    for s in (0, 1):
        srv.submit(_q(data, seed=s, memory_budget_bytes=budget))
    srv.drain()
    assert srv.stats["batches"] == 0
    assert srv.stats["singleton_queries"] == 2


def test_early_stop_queries_run_singleton():
    """tolerance/target_loss queries need per-query epoch control: they
    must not be fused (and still complete correctly)."""
    data = synthetic.dense_classification(RNG, 96, 4)
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    t1 = srv.submit(_q(data, seed=0, tolerance=1e-3))
    t2 = srv.submit(_q(data, seed=1, tolerance=1e-3))
    srv.drain()
    assert srv.stats["batches"] == 0
    assert srv.stats["singleton_queries"] == 2
    assert t1.result.batch_size == 1 and t2.result.batch_size == 1


def test_heterogeneous_epochs_fuse_via_masked_lanes():
    """Queries differing ONLY in their epoch budget fuse into one
    masked-lane batch, and each lane returns exactly its own singleton
    result (the lane freezes once its budget is spent)."""
    data = synthetic.dense_classification(RNG, 96, 4)
    hints = {"ordering": "shuffle_once", "scheme": "serial"}
    budgets = (1, 3, 2)
    eng = engine.Engine()
    serial = [
        eng.run(_q(data, seed=s, epochs=e, hints=hints))
        for s, e in enumerate(budgets)
    ]
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    tickets = [
        srv.submit(_q(data, seed=s, epochs=e, hints=hints))
        for s, e in enumerate(budgets)
    ]
    srv.drain()
    assert srv.stats["batches"] == 1
    assert srv.stats["masked_batches"] == 1
    for t, ref in zip(tickets, serial):
        assert t.error is None
        assert t.result.epochs == ref.epochs
        np.testing.assert_allclose(
            np.asarray(t.result.model), np.asarray(ref.model),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            t.result.losses[-1], ref.losses[-1], rtol=1e-5
        )


def test_incompatible_queries_are_not_fused():
    """Different task_args -> different cache key fields -> no fusion
    (epoch budgets no longer separate keys — masked lanes fuse them)."""
    data = synthetic.dense_classification(RNG, 96, 4)
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    srv.submit(_q(data, seed=0))
    srv.submit(engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 4, "mu": 1e-3},
        seed=1, epochs=2, tolerance=0.0,
    ))
    srv.drain()
    assert srv.stats["batches"] == 0
    assert srv.stats["singleton_queries"] == 2


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_sheds_load_beyond_queue_bound():
    data = synthetic.dense_classification(RNG, 64, 4)
    srv = serve.ServingEngine(
        serve.ServeConfig(max_queue=2, max_per_task=8, max_batch=8)
    )
    tickets = [srv.submit(_q(data, seed=s)) for s in range(4)]
    verdicts = [t.accepted for t in tickets]
    assert verdicts == [True, True, False, False]
    assert tickets[2].reject_reason == serve.REJECT_QUEUE_FULL
    assert tickets[3].done is False and tickets[3].result is None
    assert srv.drain() == 2
    assert all(t.done for t in tickets[:2])
    assert srv.stats["rejected"] == 2
    assert srv.stats["shed_queue_full"] == 2
    assert srv.stats["shed_task_limit"] == 0
    m = srv.metrics()
    assert m["shed_queue_full"] == 2
    assert m["queue_depth"] == 0
    assert m["obs"]["serve.shed.queue_full"]["value"] == 2
    assert m["obs"]["serve.accepted"]["value"] == 2
    # per-task latency histogram saw both served queries
    lat = m["obs"]["serve.latency_s.logreg"]
    assert lat["count"] == 2 and lat["p99"] >= lat["p50"] > 0


def test_admission_per_task_limit():
    data = synthetic.dense_classification(RNG, 64, 4)
    srv = serve.ServingEngine(
        serve.ServeConfig(max_queue=8, max_per_task=1, max_batch=8)
    )
    t1 = srv.submit(_q(data, seed=0))
    t2 = srv.submit(_q(data, seed=1))  # same task: over the limit
    t3 = srv.submit(
        engine.AnalyticsQuery(task="svm", data=data, task_args={"dim": 4},
                              epochs=1, tolerance=0.0)
    )  # different task: admitted
    assert t1.accepted and t3.accepted
    assert not t2.accepted
    assert t2.reject_reason == serve.REJECT_TASK_LIMIT
    assert srv.stats["shed_task_limit"] == 1
    assert srv.stats["shed_queue_full"] == 0
    srv.drain()
    assert t1.done and t3.done


def test_failed_query_completes_with_error_and_does_not_kill_the_queue():
    """A query that cannot be planned must not strand the rest of the
    queue: its ticket completes with ``error`` set, later queries run."""
    data = synthetic.dense_classification(RNG, 64, 4)
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    bad = srv.submit(_q(data, hints={"ordering": "no_such_ordering"}))
    good = srv.submit(_q(data, seed=1))
    srv.drain()
    assert bad.done and bad.result is None and bad.error
    assert "no_such_ordering" in bad.error
    assert good.done and good.result is not None and good.error is None
    assert srv.stats["failed_queries"] == 1


def test_tickets_stamp_when_they_left_the_queue():
    data = synthetic.dense_classification(RNG, 64, 4)
    srv = serve.ServingEngine(
        serve.ServeConfig(max_queue=2, max_per_task=8, max_batch=1)
    )
    tickets = [srv.submit(_q(data, seed=s)) for s in range(3)]
    assert tickets[2].dequeue_s is None  # refused at admission
    assert all(t.dequeue_s is None for t in tickets[:2])
    srv.drain()
    first, second = tickets[:2]
    for t in (first, second):
        assert t.submit_s <= t.dequeue_s <= t.done_s
    # one lane per pump: the second waited while the first ran
    assert second.dequeue_s >= first.done_s
    assert tickets[2].dequeue_s is None


def test_slowest_pump_keeps_the_longest_pump_and_its_spans():
    """The record is replaced only by a longer pump, and names what the
    pump waited on: main-thread CPU against the wall, compile events,
    and the spans the flight ring recorded inside it."""
    from repro import obs
    from repro.obs import export

    data = synthetic.dense_classification(RNG, 64, 4)
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=1))
    assert srv.slowest_pump is None
    srv.submit(_q(data, seed=0))
    srv.pump()  # cold: plans and compiles
    cold = srv.slowest_pump
    assert cold["lanes"] == 1 and cold["queue_depth"] == 1
    assert cold["traces"] > 0 and cold["compiles"] > 0
    names = [n for n, _ in cold["spans"]]
    assert "serve.pump" in names and "engine.epoch" in names
    assert "engine.sync" in names and "engine.order" in names

    srv.submit(_q(data, seed=1))
    srv.pump()  # warm, and shorter: the record stays
    assert srv.slowest_pump is cold

    run = srv.engine.run
    stall = 2.0 * cold["wall_s"]

    def stalled(query):
        with obs.span("test.stall"):
            time.sleep(stall)
        return run(query)

    srv.engine.run = stalled
    for s in (2, 3):
        srv.submit(_q(data, seed=s))
    srv.pump()
    slow = srv.slowest_pump
    assert slow is not cold and slow["wall_s"] >= stall
    assert slow["queue_depth"] == 2 and slow["lanes"] == 1
    assert slow["cpu_s"] < slow["wall_s"] - 0.5 * stall  # it slept
    assert slow["compiles"] == 0 and slow["cache_loads"] == 0
    assert dict(slow["spans"])["test.stall"] >= stall
    assert [n for n, _ in slow["spans"]][-1] == "serve.pump"
    snap = export.snapshot_payload()["metrics"]["serve.slowest_pump"]
    assert snap["value"] is slow


# ---------------------------------------------------------------------------
# persistent plan cache
# ---------------------------------------------------------------------------


def test_persistent_cache_warm_start_reprobes_nothing(tmp_path):
    """A fresh engine in a 'new process' (empty probe cache) pointed at a
    populated PlanStore must re-probe and re-plan nothing."""
    data = synthetic.dense_classification(RNG, 128, 4)
    q = _q(data)
    first = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    rep1 = first.explain(q)
    assert first.stats["plans_computed"] == 1

    probes.clear_cache()  # simulated process restart
    runs_before = probes.stats["probe_runs"]
    second = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    rep2 = second.explain(q)
    assert probes.stats["probe_runs"] == runs_before, "warm start re-probed"
    assert second.stats["plans_computed"] == 0, "warm start re-planned"
    assert second.stats["plan_disk_hits"] == 1
    assert rep2.chosen == rep1.chosen
    assert rep2.describe() == rep1.describe()
    # the loaded plan executes
    res = second.run(q)
    assert np.isfinite(res.losses[-1])


def test_persistent_cache_invalidates_on_different_table(tmp_path):
    """Same shape, different contents: the stored statistics are stale
    and the entry must read as a miss."""
    d1 = synthetic.dense_classification(RNG, 128, 4)
    d2 = jax.tree.map(lambda x: x + 1.0, d1)  # same signature, new table
    e1 = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    e1.explain(_q(d1))
    e2 = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    e2.explain(_q(d2))
    assert e2.stats["plan_disk_hits"] == 0
    assert e2.stats["plans_computed"] == 1


def test_plan_key_names_the_platform(tmp_path, monkeypatch):
    """A plan probed on one platform is no plan for another: a PlanStore
    entry written under the CPU reads as a miss on a TPU (the probed
    rates and the kernel choice would be the CPU's)."""
    from repro.engine import executor

    q = _q(synthetic.dense_classification(RNG, 128, 4))
    dev = jax.local_devices()[0]
    e1 = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    e1.explain(q)
    key = e1._query_plan_key(q)
    assert key[-2:] == (dev.platform, dev.device_kind)
    assert e1.plan_store.load(key, q) is not None
    monkeypatch.setattr(
        executor, "device_key",
        lambda: (jax.local_device_count(), "tpu", "TPU v5 lite"),
    )
    e2 = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    assert e2.plan_store.load(e2._query_plan_key(q), q) is None
    e2.explain(q)
    assert e2.stats["plan_disk_hits"] == 0
    assert e2.stats["plans_computed"] == 1


def test_a_format_3_entry_is_replanned_and_rewritten(tmp_path):
    """Format 3 stored plans with the shared-memory scheme's fields and
    the minibatch kernel's probed rate. Such an entry reads as a miss:
    the next run re-plans the query and rewrites the entry at format 4."""
    import json

    q = _q(synthetic.dense_classification(RNG, 128, 4))
    e1 = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    e1.explain(q)
    path = e1.plan_store._path(e1._query_plan_key(q))
    with open(path) as f:
        entry = json.load(f)
    assert serve.FORMAT_VERSION == 4 and entry["version"] == 4
    report = entry["report"]
    for p in [report["chosen"]] + [c["plan"] for c in report["candidates"]]:
        p.update(sm_scheme="nolock", sm_workers=8)
    report["calibration"]["impl_per_row"]["pallas_minibatch"] = 1e-8
    with open(path, "w") as f:
        json.dump(dict(entry, version=3), f)

    probes.clear_cache()
    e2 = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    rep = e2.explain(q)
    assert e2.stats["plan_disk_hits"] == 0
    assert e2.stats["plans_computed"] == 1
    with open(path) as f:
        entry = json.load(f)
    assert entry["version"] == 4
    assert "sm_scheme" not in entry["report"]["chosen"]
    assert "pallas_minibatch" not in (
        entry["report"]["calibration"]["impl_per_row"])
    assert e2.plan_store.load(e2._query_plan_key(q), q).chosen == rep.chosen


def test_fingerprint_catches_interior_reorder():
    """A same-multiset, interior-only reordering (label-clustered vs
    shuffled — exactly the statistic the planner keys on) must change
    the content fingerprint even though every boundary row is equal."""
    d1 = synthetic.dense_classification(RNG, 128, 4)
    perm = np.concatenate([
        np.arange(4),
        np.random.default_rng(0).permutation(np.arange(4, 124)),
        np.arange(124, 128),
    ])
    d2 = jax.tree.map(lambda a: a[perm], d1)
    f1 = _q(d1).content_fingerprint()
    f2 = _q(d2).content_fingerprint()
    assert f1 != f2


def test_serving_engine_uses_disk_cache(tmp_path):
    data = synthetic.dense_classification(RNG, 96, 4)
    cfg = serve.ServeConfig(max_batch=4, cache_dir=str(tmp_path))
    srv1 = serve.ServingEngine(cfg)
    srv1.submit(_q(data))
    srv1.drain()
    srv2 = serve.ServingEngine(cfg)  # same dir, fresh engine
    srv2.submit(_q(data))
    srv2.drain()
    assert srv2.engine.stats["plan_disk_hits"] == 1
    assert srv2.engine.stats["plans_computed"] == 0


def test_serving_engine_registers_operational_gauges(tmp_path):
    """Queue depth and plan-store size are live callback gauges: they
    read the engine's actual state at snapshot time, not a stale copy."""
    from repro import obs

    data = synthetic.dense_classification(RNG, 64, 4)
    srv = serve.ServingEngine(
        serve.ServeConfig(max_batch=4, cache_dir=str(tmp_path))
    )
    srv.submit(_q(data, seed=0))
    srv.submit(_q(data, seed=1))
    snap = obs.metrics.snapshot("serve.")
    assert snap["serve.queue_depth"]["value"] == 2
    assert snap["serve.plan_store_entries"]["value"] == 0
    srv.drain()
    snap = obs.metrics.snapshot("serve.")
    assert snap["serve.queue_depth"]["value"] == 0
    assert snap["serve.plan_store_entries"]["value"] >= 1


# ---------------------------------------------------------------------------
# trace-count observables
# ---------------------------------------------------------------------------


def test_loss_retraces_do_not_inflate_epoch_trace_count():
    """The per-epoch objective evaluation (stop rules) retraces on its
    own counter; the epoch executable's count stays pure."""
    data = synthetic.dense_classification(RNG, 96, 4)
    eng = engine.Engine()
    res = eng.run(_q(data, epochs=3, tolerance=1e-9))
    assert res.trace_count == 1
    assert res.loss_trace_count >= 1


def test_describe_survives_empty_losses():
    data = synthetic.dense_classification(RNG, 64, 4)
    res = engine.Engine().run(_q(data, epochs=0))
    assert res.losses == []
    assert "loss=n/a" in res.describe()


# ---------------------------------------------------------------------------
# MRS double-buffer swap (executor regression)
# ---------------------------------------------------------------------------


def test_mrs_buffer_swap_cycles_reservoir():
    """_execute's buf_a/buf_b swap must hand the memory worker *last*
    epoch's reservoir each epoch (run_mrs semantics). The reference below
    replays the executor's exact rng stream with the canonical swap; a
    broken swap (e.g. feeding the memory worker a stale zero buffer, or
    never activating it) diverges from this model."""
    data = synthetic.dense_classification(RNG, 64, 4)
    seed, epochs, buf_rows = 5, 3, 16
    plan = engine.Plan("clustered", "mrs", mrs_buffer=buf_rows)
    res = engine.Engine().run(_q(data, seed=seed, epochs=epochs), plan=plan)

    spec = catalog.get("logreg")
    task = spec.make_task(dim=4)
    agg = uda_lib.IGDAggregate(task, spec.step_size(64), prox=spec.prox(task))
    cfg = mrs_lib.MRSConfig(buffer_size=buf_rows, ratio=plan.mrs_ratio)
    rng = jax.random.PRNGKey(seed)
    perm_rng = jax.random.fold_in(rng, engine.executor.PERM_STREAM_SALT)
    state = agg.initialize(rng)
    zero = jax.tree.map(
        lambda x: jnp.zeros((buf_rows,) + x.shape[1:], x.dtype), data
    )
    buf_a, buf_b, active = zero, zero, False
    epoch_fn = jax.jit(
        lambda st, ba, bb, act, key: mrs_lib.mrs_epoch(
            agg, st, data, ba, bb, act, cfg, key
        )
    )
    for _ in range(epochs):
        # clustered ordering consumes no rng; the executor then splits
        perm_rng, sub = jax.random.split(perm_rng)
        state, buf_a = epoch_fn(state, buf_a, buf_b, jnp.bool_(active), sub)
        buf_a, buf_b = buf_b, buf_a  # memory worker gets the fresh reservoir
        active = True
    np.testing.assert_allclose(
        np.asarray(res.model), np.asarray(agg.terminate(state)),
        rtol=1e-5, atol=1e-7,
    )
