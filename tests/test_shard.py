"""repro.engine.shard: k=1 bit-parity with the singleton executor, merge
determinism, simulator-vs-real convergence ordering, planner behavior on
single/multi-device meshes, the mesh helper's env handling, the
segmented-fold weight regression, and the persistent compilation cache
opt-in."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import engine, tasks
from repro.core import igd, parallel, uda
from repro.data import synthetic
from repro.engine import serve, shard as shard_lib, xla_cache
from repro.launch import mesh as mesh_lib

RNG = jax.random.PRNGKey(0)


def _q(data, seed=0, **kw):
    kw.setdefault("epochs", 3)
    kw.setdefault("tolerance", 0.0)
    return engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 4}, seed=seed, **kw
    )


def _sharded_plan(ordering="clustered", k=1, h=1, d=1, unroll=1):
    return engine.Plan(
        ordering, "serial", unroll=unroll, parallelism="sharded",
        num_shards=k, merge_period=h, shard_devices=d,
    )


# ---------------------------------------------------------------------------
# equivalence with the singleton executor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "ordering", ["clustered", "shuffle_once", "shuffle_always"]
)
def test_sharded_k1_bit_identical_to_singleton(ordering):
    """sharded(k=1) must reproduce Engine.run exactly — same floats, not
    just close: same rng streams, same fold, no compensation at k=1."""
    data = synthetic.dense_classification(RNG, 96, 4)
    q = _q(data, seed=7)
    eng = engine.Engine()
    base = eng.run(q, plan=engine.Plan(ordering, "serial"))
    sh = eng.run(q, plan=_sharded_plan(ordering, k=1))
    assert np.array_equal(np.asarray(base.model), np.asarray(sh.model))
    assert base.losses == sh.losses
    assert sh.epochs == base.epochs


def test_sharded_k1_bit_identical_with_stop_rule():
    """Block-boundary loss evaluation at H=1 equals the singleton's
    per-epoch evaluation, so early-stop behavior is identical too."""
    data = synthetic.dense_classification(RNG, 96, 4)
    q = _q(data, epochs=8, tolerance=1e-2)
    eng = engine.Engine()
    base = eng.run(q, plan=engine.Plan("shuffle_once", "serial"))
    sh = eng.run(q, plan=_sharded_plan("shuffle_once", k=1))
    assert np.array_equal(np.asarray(base.model), np.asarray(sh.model))
    assert base.losses == sh.losses
    assert base.epochs == sh.epochs and base.converged == sh.converged


def test_sharded_merge_deterministic_and_cached():
    """k>1 under a fixed rng: bit-identical across runs, and the repeat
    query reuses the compiled blocks (no retrace)."""
    data = synthetic.dense_classification(RNG, 96, 4)
    q = _q(data)
    eng = engine.Engine()
    plan = _sharded_plan(k=4, h=2)
    r1 = eng.run(q, plan=plan)
    traces = r1.trace_count
    assert traces >= 1
    r2 = eng.run(q, plan=plan)
    assert np.array_equal(np.asarray(r1.model), np.asarray(r2.model))
    assert r2.trace_count == traces, "repeat sharded query retraced"


def test_sharded_matches_segmented_reference():
    """One H=1 clustered sharded epoch == segmented_fold with the
    compensated schedule (the paper's pure-UDA semantics)."""
    data = synthetic.dense_classification(RNG, 96, 4)
    q = _q(data, epochs=1)
    eng = engine.Engine()
    res = eng.run(q, plan=_sharded_plan(k=4))

    spec = engine.get("logreg")
    task = spec.make_task(dim=4)
    agg = uda.IGDAggregate(
        task, shard_lib.compensated_step_size(spec.step_size(96), 4),
        prox=spec.prox(task),
    )
    st = agg.initialize(jax.random.PRNGKey(0))
    ref = uda.segmented_fold(agg, st, data, 4)
    np.testing.assert_allclose(
        np.asarray(res.model), np.asarray(ref.model), rtol=1e-6, atol=1e-8
    )


def test_sharded_quality_and_simulator_ordering():
    """The satellite check: the real sharded path converges, and the
    shared-memory simulator's quality ordering (lock >= aig >= nolock)
    matches the paper's Fig. 9(A) story."""
    data = synthetic.dense_classification(RNG, 1024, 12, clustered=False)
    task = tasks.LogisticRegression(dim=12)
    base = float(task.full_loss(task.init_model(RNG), data))

    q = engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 12},
        epochs=4, tolerance=0.0,
    )
    res = engine.Engine().run(q, plan=_sharded_plan(k=8, h=2))
    assert res.losses[-1] < 0.5 * base  # the real sharded path converges

    step = igd.diminishing(0.3, decay=1024)
    losses = {}
    for scheme in ("lock", "aig", "nolock"):
        cfg = parallel.SharedMemoryConfig(scheme=scheme, workers=8)
        _, ls = parallel.run_shared_memory(
            task, step, data, rng=RNG, epochs=4, cfg=cfg,
            loss_fn=task.full_loss,
        )
        losses[scheme] = ls[-1]
    slack = 0.02 * base
    assert losses["lock"] <= losses["aig"] + slack
    assert losses["lock"] <= losses["nolock"] + slack


def test_segmented_fold_weight_stays_bounded():
    """Regression: re-segmenting a merged state compounded the merge
    weight x(k+1) per epoch — float32 overflow, NaN models by epoch ~40."""
    data = synthetic.dense_classification(RNG, 96, 4)
    task = tasks.LogisticRegression(dim=4)
    agg = uda.IGDAggregate(task, igd.diminishing(0.3, decay=96))
    st = agg.initialize(RNG)
    for _ in range(60):
        st = uda.segmented_fold(agg, st, data, 8)
    assert np.isfinite(np.asarray(st.model)).all()
    assert float(st.weight) == 60 * 96


# ---------------------------------------------------------------------------
# planner integration
# ---------------------------------------------------------------------------


def test_planner_single_device_stays_singleton():
    """Without a multi-device mesh there is no sharded plan axis: no
    probes run, no sharded candidates are enumerated (tests run on the
    single CPU device)."""
    data = synthetic.dense_classification(RNG, 128, 4)
    rep = engine.Engine().explain(_q(data))
    assert rep.chosen.parallelism == "singleton"
    assert not any(
        c.plan.parallelism == "sharded" for c in rep.candidates
    )
    assert rep.calibration.shard == {}
    assert rep.calibration.device_count == jax.local_device_count()


def test_nonconvex_task_caps_sharded_plans():
    """Model averaging of misaligned non-convex factors diverges at high
    shard counts (measured for lmf): the planner caps them."""
    from repro.engine import planner, probes

    point = probes.ShardPoint(
        num_shards=8, devices=2, epoch_seconds_per_row=1e-7,
        block_seconds=1e-3, unroll=8,
    )
    cal = probes.Calibration(
        shuffle_per_row=1e-6, fold_per_row={1: 2e-7}, merge_seconds=1e-4,
        probe_rows=256, seg_per_row={}, shard={8: point}, device_count=8,
    )
    rdata = synthetic.ratings(RNG, 32, 16, 512, rank=2)
    q_lmf = engine.AnalyticsQuery(
        task="lmf", data=rdata,
        task_args={"n_rows": 32, "n_cols": 16, "rank": 4}, epochs=4,
    )
    q_cvx = _q(synthetic.dense_classification(RNG, 512, 4), epochs=4)
    lmf_ks = {p.num_shards for p in planner.enumerate_plans(q_lmf, 1, cal)
              if p.parallelism == "sharded"}
    cvx_ks = {p.num_shards for p in planner.enumerate_plans(q_cvx, 1, cal)
              if p.parallelism == "sharded"}
    assert cvx_ks == {8}
    assert lmf_ks == {planner.NONCONVEX_SHARD_CAP}


def test_shard_probe_picks_a_count_that_divides_the_table():
    """The planner enumerates only shard counts that divide the table, so
    the mesh probe must measure such a count: a 2,052-row table (4 x 513,
    not a multiple of 8) is probed at k=4 on its 2,048-row slab, not at
    k=8 (which left Forest's 581,012 rows with no sharded candidate)."""
    from repro.engine import probes

    n = 2052
    data = synthetic.dense_classification(RNG, n, 4)
    agg = uda.IGDAggregate(
        tasks.LogisticRegression(dim=4), igd.diminishing(0.5, decay=n)
    )
    slab = jax.tree.map(lambda x: x[:probes.SHARD_PROBE_ROWS], data)
    shard = probes._probe_sharded(agg, slab, agg.initialize(RNG), n)
    assert set(shard) == {4}


def test_invalid_sharded_hints_and_plans_are_rejected():
    data = synthetic.dense_classification(RNG, 96, 4)
    eng = engine.Engine()
    with pytest.raises(ValueError, match="merge_period"):
        eng.explain(_q(data, hints={"parallelism": "sharded",
                                    "num_shards": 2, "merge_period": 0}))
    with pytest.raises(ValueError, match="implies scheme='serial'"):
        eng.explain(_q(data, hints={"parallelism": "sharded",
                                    "scheme": "segmented",
                                    "num_shards": 2}))
    # a forced plan bypasses the planner; execution must still refuse
    # (merge_period=0 would loop forever)
    with pytest.raises(ValueError, match="merge_period"):
        eng.run(_q(data), plan=_sharded_plan(k=2, h=0))


def test_hint_forced_sharded_plan_enumerates_and_runs():
    data = synthetic.dense_classification(RNG, 96, 4)
    q = _q(data, hints={"parallelism": "sharded", "num_shards": 4,
                        "merge_period": 3})
    eng = engine.Engine()
    rep = eng.explain(q)
    assert rep.chosen.parallelism == "sharded"
    assert rep.chosen.num_shards == 4 and rep.chosen.merge_period == 3
    res = eng.run(q)
    assert res.epochs == q.epochs and np.isfinite(res.losses[-1])


def test_plan_report_roundtrips_shard_fields(tmp_path):
    """PlanStore persists the grown Plan + Calibration (FORMAT_VERSION 2)
    and a fresh engine re-plans nothing."""
    data = synthetic.dense_classification(RNG, 128, 4)
    q = _q(data)
    store = serve.PlanStore(str(tmp_path))
    first = engine.Engine(plan_store=store)
    rep1 = first.explain(q)
    second = engine.Engine(plan_store=serve.PlanStore(str(tmp_path)))
    rep2 = second.explain(q)
    assert second.stats["plan_disk_hits"] == 1
    assert rep2.chosen == rep1.chosen
    assert rep2.calibration.seg_per_row == rep1.calibration.seg_per_row
    assert rep2.describe() == rep1.describe()


# ---------------------------------------------------------------------------
# serving: fused sharded batches
# ---------------------------------------------------------------------------


def test_serve_fused_sharded_batch_matches_singleton_runs():
    """Same-key sharded queries over one shared table fuse along a query
    axis and must return each query's singleton result."""
    data = synthetic.dense_classification(RNG, 96, 4)
    # ordering pinned: fusion requires the clustered (pre-partitioned)
    # stream, and this test is about fusion parity, not plan choice
    hints = {"parallelism": "sharded", "num_shards": 2, "merge_period": 2,
             "ordering": "clustered"}
    queries = [_q(data, seed=s, hints=hints) for s in (0, 1, 2)]
    eng = engine.Engine()
    serial = [eng.run(q) for q in queries]
    assert serial[0].plan.parallelism == "sharded"

    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    tickets = [srv.submit(q) for q in queries]
    srv.drain()
    assert srv.stats["batches"] == 1
    assert srv.stats["batched_queries"] == 3
    for t, ref in zip(tickets, serial):
        assert t.error is None
        assert t.result.batch_size == 3
        np.testing.assert_allclose(
            np.asarray(t.result.model), np.asarray(ref.model),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            t.result.losses[-1], ref.losses[-1], rtol=1e-5
        )


def test_serve_sharded_distinct_tables_fall_back_to_singleton():
    d1 = synthetic.dense_classification(RNG, 96, 4)
    d2 = jax.tree.map(lambda x: x * 1.25, d1)
    hints = {"parallelism": "sharded", "num_shards": 2, "merge_period": 1,
             "ordering": "clustered"}
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    t1 = srv.submit(_q(d1, seed=0, hints=hints))
    t2 = srv.submit(_q(d2, seed=1, hints=hints))
    srv.drain()
    assert srv.stats["batches"] == 0
    assert srv.stats["singleton_queries"] == 2
    assert t1.error is None and t2.error is None
    assert t1.result is not None and t2.result is not None


# ---------------------------------------------------------------------------
# launch.mesh helper (env-respecting host-device forcing)
# ---------------------------------------------------------------------------


def test_force_host_device_count_env_editing():
    env = {"XLA_FLAGS": "--xla_cpu_enable_fast_math=false"}
    assert mesh_lib.force_host_device_count(8, env=env) == 8
    assert "--xla_cpu_enable_fast_math=false" in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]

    # an existing larger request is respected...
    env2 = {"XLA_FLAGS": "--xla_force_host_platform_device_count=512"}
    assert mesh_lib.force_host_device_count(8, env=env2) == 512
    assert env2["XLA_FLAGS"].count("device_count") == 1
    # ...a smaller one is raised to cover the request
    env3 = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    assert mesh_lib.force_host_device_count(8, env=env3) == 8
    assert "device_count=8" in env3["XLA_FLAGS"]
    # override always wins
    assert mesh_lib.force_host_device_count(4, env=env3, override=True) == 4
    assert "device_count=4" in env3["XLA_FLAGS"]
    assert env3["XLA_FLAGS"].count("device_count") == 1


def test_dryrun_import_no_longer_mutates_env():
    flags_before = os.environ.get("XLA_FLAGS")
    import repro.launch.dryrun  # noqa: F401

    assert os.environ.get("XLA_FLAGS") == flags_before


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


@pytest.fixture
def _cache_on():
    """The suite runs with JAX's cache switch off; these tests turn it
    on and restore jax's process-global cache config afterwards."""
    was_on = jax.config.jax_enable_compilation_cache
    old_dir = jax.config.jax_compilation_cache_dir
    old_state = dict(xla_cache._state)
    jax.config.update("jax_enable_compilation_cache", True)
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    jax.config.update("jax_compilation_cache_dir", old_dir)
    xla_cache._state.update(old_state)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def test_xla_cache_enabled_by_env(_cache_on, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins, and the module sets no directory
    of its own: jax read the variable itself."""
    path = str(tmp_path / "from_env")
    before = jax.config.jax_compilation_cache_dir
    assert xla_cache.maybe_enable(env={xla_cache.ENV_VAR: path})
    assert xla_cache.status() == {"path": path, "error": None}
    assert jax.config.jax_compilation_cache_dir == before


def test_xla_cache_disabled_without_env(_cache_on, tmp_path, monkeypatch):
    """Without the variable the cache sits at a fixed, gitignored path
    in the checkout (redirected here so the test writes no cache files
    into the tree), and the engine's executables land in it."""
    root = os.path.join(os.path.dirname(__file__), "..")
    assert xla_cache.DEFAULT_DIR == os.path.normpath(
        os.path.join(root, ".jax_cache")
    )
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    path = str(tmp_path / "jax_cache")
    monkeypatch.setattr(xla_cache, "DEFAULT_DIR", path)
    assert xla_cache.maybe_enable(env={})
    assert jax.config.jax_compilation_cache_dir == path
    assert xla_cache.status() == {"path": path, "error": None}
    eng = engine.Engine()
    eng.run(_q(synthetic.dense_classification(RNG, 64, 4), epochs=1))
    assert os.listdir(path), "no executable was persisted"
    # JAX's switch off: nothing is enabled
    jax.config.update("jax_enable_compilation_cache", False)
    xla_cache._state["path"] = None
    assert not xla_cache.maybe_enable(env={})


# ---------------------------------------------------------------------------
# multi-device: a real forced mesh in a subprocess (kept tiny)
# ---------------------------------------------------------------------------

_SCRIPT_MESH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from repro import engine
from repro.data import synthetic

assert jax.local_device_count() == 4
data = synthetic.dense_classification(jax.random.PRNGKey(0), 64, 4)
q = engine.AnalyticsQuery(task="logreg", data=data, task_args={"dim": 4},
                          epochs=2, tolerance=0.0)
eng = engine.Engine()
mk = lambda d: engine.Plan("clustered", "serial", parallelism="sharded",
                           num_shards=4, merge_period=2, shard_devices=d)
r1 = eng.run(q, plan=mk(1))
r4 = eng.run(q, plan=mk(4))
# the merge tree's float association differs across placements; the
# result must agree to float tolerance and be placement-independent
np.testing.assert_allclose(np.asarray(r1.model), np.asarray(r4.model),
                           rtol=1e-5, atol=1e-7)
print("SHARD_MESH_OK")
"""


def test_sharded_on_forced_mesh_is_placement_independent():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    )
    env["JAX_PLATFORMS"] = "cpu"  # a forced-host-device mesh, never the chip
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT_MESH], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert "SHARD_MESH_OK" in out.stdout, (
        out.stdout[-2000:], out.stderr[-3000:],
    )
