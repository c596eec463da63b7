"""A step the query states: ``task_args["alpha0"]`` replaces the
catalog schedule's first step for any technique (``catalog.stated_step``
via ``Engine._aggregate_for``, the one place every driver builds its
aggregate), enters every cache key through ``task_args``, and shows in
EXPLAIN and on the ``engine.run`` span. Without it a query gets exactly
the catalog's schedule."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs
from repro.data import synthetic
from repro.engine import catalog, planner, probes, serve

RNG = jax.random.PRNGKey(0)
# one plan for every query compared below: probe timings on a loaded
# host may rank plans differently for two plan keys
PINNED = {"ordering": "shuffle_always", "scheme": "serial",
          "parallelism": "singleton", "implementation": "xla_fold"}


def _dense(n=96):
    return synthetic.dense_classification(RNG, n, 4)


def _q(task_args, data=None, task="logreg", **kw):
    kw.setdefault("tolerance", 0.0)
    kw.setdefault("epochs", 2)
    return engine.AnalyticsQuery(task=task, data=_dense() if data is None
                                 else data, task_args=task_args, **kw)


def test_a_stated_step_replaces_only_alpha0():
    _, task, agg = engine.Engine()._aggregate_for(_q({"dim": 4,
                                                      "alpha0": 0.05}))
    catalog_step = catalog.get("logreg").step_size(96)
    assert agg.step_size.alpha0 == 0.05
    assert agg.step_size.kind == catalog_step.kind
    assert agg.step_size.decay == catalog_step.decay
    assert not hasattr(task, "alpha0")  # never reaches the factory


@pytest.mark.parametrize("name", catalog.names())
def test_without_a_stated_step_the_schedule_is_the_catalogs(name):
    spec = catalog.get(name)
    assert spec.schedule(1000) == spec.step_size(1000)


def test_the_catalogs_alpha0_stated_gives_the_same_floats():
    data = _dense()
    eng = engine.Engine()
    plan = engine.Plan("shuffle_always", "serial", unroll=1)
    plain = eng.run(_q({"dim": 4}, data), plan=plan)
    stated = eng.run(_q({"dim": 4, "alpha0": 0.5}, data),  # the catalog's
                     plan=plan)
    assert np.array_equal(np.asarray(plain.model), np.asarray(stated.model))
    assert plain.losses == stated.losses


def test_alpha0_separates_the_plan_compile_and_fused_keys():
    data = _dense()
    a = _q({"dim": 4}, data, hints=PINNED)
    b = _q({"dim": 4, "alpha0": 0.05}, data, hints=PINNED)
    eng = engine.Engine()
    assert eng._query_plan_key(a) != eng._query_plan_key(b)
    eng.run(a)
    eng.run(b)
    assert eng.cache_info()["compiled_plans"] == 2
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    assert srv._batch_key(a) != srv._batch_key(b)
    tickets = [srv.submit(q) for q in (a, b, a, b)]
    srv.drain()
    assert srv.stats["batches"] == 2  # one fused call per step
    np.testing.assert_allclose(np.asarray(tickets[1].result.model),
                               np.asarray(eng.run(b).model), rtol=1e-6)


@pytest.mark.parametrize("bad", [0, 0.0, -0.1, math.nan, math.inf, True,
                                 "0.1", None])
def test_a_step_that_is_not_a_positive_number_raises(bad):
    with pytest.raises(ValueError, match="alpha0"):
        engine.Engine()._aggregate_for(_q({"dim": 4, "alpha0": bad}))


def _star_ratings(n_rows=128, n_cols=64, n=4096):
    d = synthetic.ratings(RNG, n_rows, n_cols, n, rank=3)
    return dict(d, v=jnp.clip(jnp.round(3.6 + 1.1 * d["v"] / jnp.std(d["v"])),
                              1.0, 5.0))


def test_at_a_star_scale_the_catalog_step_diverges_and_a_stated_one_fits():
    """Ratings of whole stars 1-5, rank 50: lmf's catalog step (0.1)
    goes non-finite in its first epochs, a stated 0.01 does not and
    lowers the objective."""
    data = _star_ratings()
    args = {"n_rows": 128, "n_cols": 64, "rank": 50}
    hints = {"scheme": "serial", "ordering": "shuffle_always"}
    eng = engine.Engine()
    catalog_fit = eng.run(_q(args, data, task="lmf", hints=hints))
    assert not math.isfinite(catalog_fit.losses[-1])
    stated = _q(dict(args, alpha0=0.01), data, task="lmf", hints=hints,
                epochs=2, target_loss=-math.inf)
    fit = eng.run(stated)
    assert all(math.isfinite(x) for x in fit.losses)
    assert fit.losses[1] < fit.losses[0]
    assert np.all(np.isfinite(np.asarray(fit.model["L"])))


def test_explain_and_the_run_span_say_where_the_step_came_from():
    data = _dense()
    eng = engine.Engine()
    text = eng.explain(_q({"dim": 4}, data)).describe()
    assert "step   : diminishing, alpha0=0.5 (the catalog's)" in text
    stated = _q({"dim": 4, "alpha0": 0.05}, data)
    assert "alpha0=0.05 (stated by the query)" in eng.explain(
        stated).describe()
    with obs.tracing() as rec:
        eng.run(stated)
    run = [s for s in rec.spans if s["name"] == "engine.run"]
    assert run and run[0]["attrs"]["alpha0"] == 0.05


def test_segment_averaging_is_priced_for_a_nonconvex_task():
    """With segments measured 8x cheaper per row than the serial fold,
    a convex task takes the segmented plan; lmf, whose averaged factors
    keep ~1/k of each row's progress, keeps the serial one."""
    cal = probes.Calibration(
        shuffle_per_row=1e-9, fold_per_row={1: 8e-6}, merge_seconds=1e-6,
        probe_rows=256, seg_per_row={8: 1e-6},
    )
    q = _q({"dim": 4}, _dense(1024))
    serial = engine.Plan("clustered", "serial")
    seg = engine.Plan("clustered", "segmented", num_segments=8)

    def cost(plan, nonconvex):
        return planner.program_cost(plan, q, cal, 0.0, True,
                                    nonconvex).cost_seconds

    assert cost(seg, False) < cost(serial, False)
    assert cost(seg, True) > cost(serial, True)
