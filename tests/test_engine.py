"""repro.engine: catalog round-trip, planner golden cases, compiled-plan
cache behavior, and the ≤30-LoC new-technique guarantee."""

import dataclasses
import inspect
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import igd, ordering
from repro.data import synthetic
from repro.engine import catalog
from repro.tasks import Task

RNG = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# import layering
# ---------------------------------------------------------------------------


def test_the_analytics_imports_load_no_lm_module():
    """The engine and its serving entry point stand apart from the LM
    stack: importing them loads no model, architecture config, attention
    or decode kernel, and no LM collective."""
    script = (
        "import sys\n"
        "import repro.engine, repro.launch.serve\n"
        "stack = ('repro.models', 'repro.configs', "
        "'repro.kernels.attention', 'repro.kernels.decode', "
        "'repro.dist.collectives')\n"
        "print(sorted(m for m in sys.modules if m.startswith(stack)))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "[]", out.stdout


# ---------------------------------------------------------------------------
# catalog
# ---------------------------------------------------------------------------


def test_catalog_has_every_builtin_technique():
    assert {"logreg", "svm", "least_squares", "sparse_logreg", "sparse_svm",
            "lmf", "crf", "kalman", "portfolio"} <= set(catalog.names())


def test_catalog_round_trip_register_lookup_run():
    """Register a brand-new technique, look it up, run it through the
    engine — with NO edits to repro/engine internals and ≤ 30 LoC."""

    # --- the entire integration of a new technique (counted below) -----
    @engine.register_task(
        "huber_t", step_size=lambda n: igd.diminishing(0.3, decay=n)
    )
    @dataclasses.dataclass(frozen=True)
    class HuberRegression(Task):
        dim: int
        delta: float = 1.0

        def init_model(self, rng):
            del rng
            return jnp.zeros((self.dim,), jnp.float32)

        def example_loss(self, w, ex):
            r = jnp.dot(w, ex["x"]) - ex["y"]
            a = jnp.abs(r)
            return jnp.where(
                a <= self.delta,
                0.5 * r * r,
                self.delta * (a - 0.5 * self.delta),
            )
    # -------------------------------------------------------------------

    try:
        loc = len(inspect.getsource(HuberRegression).strip().splitlines())
        assert loc <= 30, f"new-technique integration took {loc} LoC"
        assert catalog.get("huber_t").make_task(dim=4).dim == 4

        k1, k2 = jax.random.split(RNG)
        w_true = jax.random.normal(k1, (4,))
        x = jax.random.normal(k2, (512, 4))
        data = {"x": x, "y": x @ w_true}
        res = engine.run(
            engine.AnalyticsQuery(
                task="huber_t", data=data, task_args={"dim": 4},
                epochs=30, tolerance=1e-4,
            )
        )
        loss0 = float(
            HuberRegression(dim=4).full_loss(jnp.zeros(4), data)
        )
        assert res.losses[-1] < 0.1 * loss0
    finally:
        catalog.unregister("huber_t")


def test_catalog_rejects_duplicate_and_unknown():
    with pytest.raises(KeyError):
        catalog.get("no_such_task")
    with pytest.raises(ValueError):
        engine.register_task("logreg")(Task)


# ---------------------------------------------------------------------------
# planner golden cases
# ---------------------------------------------------------------------------


def _catx_query(n=512, **kw):
    data = ordering.make_catx_dataset(n)
    return engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 1}, epochs=30, **kw
    )


def test_planner_rejects_clustered_on_catx():
    """Label-clustered CA-TX data: every clustered-scan candidate must be
    costed out (the §3.2 pathology)."""
    rep = engine.explain(_catx_query())
    assert rep.clusteredness > 0.9
    assert rep.chosen.ordering != "clustered"
    clustered = [
        c for c in rep.candidates
        if c.plan.ordering == "clustered" and c.plan.scheme != "mrs"
    ]
    assert clustered, "planner must still enumerate clustered candidates"
    best = min(c.cost_seconds for c in rep.candidates)
    assert all(c.cost_seconds > 10 * best for c in clustered)


def test_planner_prefers_clustered_scan_on_preshuffled_data():
    """Already-random order: the shuffle buys nothing, the free stored-
    order scan must win (paper: shuffle once only when needed)."""
    data = synthetic.dense_classification(RNG, 512, 8, clustered=False)
    rep = engine.explain(
        engine.AnalyticsQuery(task="logreg", data=data,
                              task_args={"dim": 8}, epochs=10)
    )
    assert rep.clusteredness < 0.2
    assert rep.chosen.ordering == "clustered"


def test_planner_serial_beats_segmented_on_tiny_data():
    data = synthetic.dense_classification(RNG, 64, 4)
    rep = engine.explain(
        engine.AnalyticsQuery(task="svm", data=data, task_args={"dim": 4},
                              epochs=5)
    )
    assert rep.chosen.scheme == "serial"
    seg = [c for c in rep.candidates if c.plan.scheme == "segmented"]
    assert seg and all(c.cost_seconds >= rep.cost_seconds for c in seg)


def test_planner_falls_back_to_mrs_under_memory_budget():
    """Table larger than the buffer budget: shuffled-copy plans are
    infeasible, buffered MRS (§3.4) is chosen."""
    q = _catx_query(n=1024, memory_budget_bytes=1024)  # table >> budget
    rep = engine.explain(q)
    assert rep.chosen.scheme == "mrs"
    assert rep.chosen.mrs_buffer >= 8
    shuffled = [c for c in rep.candidates
                if c.plan.ordering != "clustered"]
    assert all(math.isinf(c.cost_seconds) for c in shuffled)


def test_plan_describe_is_explainable():
    rep = engine.explain(_catx_query())
    text = rep.describe()
    assert "plan   :" in text and "reject :" in text
    assert "clustered" in text and "shuffle_once" in text


# ---------------------------------------------------------------------------
# end-to-end: planner choice beats the forced pathological plan
# ---------------------------------------------------------------------------


def test_engine_planned_beats_forced_clustered_on_catx():
    n = 512
    optimum = 2 * n * float(np.log(2.0))  # logreg optimum on CA-TX is w=0
    q = _catx_query(n=n, tolerance=0.0, target_loss=1.01 * optimum)
    planned = engine.run(q)
    forced = engine.run(q, plan=engine.Plan("clustered", "serial"))
    assert planned.converged
    assert planned.epochs < forced.epochs
    assert planned.losses[-1] < forced.losses[-1]


# ---------------------------------------------------------------------------
# compiled-plan cache
# ---------------------------------------------------------------------------


def test_repeated_query_hits_compiled_plan_cache():
    """A repeated identical query must not trace or compile anything new
    (zero jit cache misses on the hot serving path)."""
    eng = engine.Engine()
    data = synthetic.dense_classification(RNG, 256, 8)
    q = engine.AnalyticsQuery(task="logreg", data=data,
                              task_args={"dim": 8}, epochs=3, tolerance=0.0)
    r1 = eng.run(q)
    assert eng.cache_info()["plan_cache_misses"] == 1
    traces_after_first = r1.trace_count
    assert traces_after_first >= 1

    r2 = eng.run(q)
    info = eng.cache_info()
    assert info["plan_cache_hits"] == 1
    assert info["compiled_plans"] == 1
    assert r2.trace_count == traces_after_first, "repeat query retraced"
    # the jitted epoch fn holds exactly one executable (one shape)
    compiled = next(iter(eng._compiled.values()))
    if hasattr(compiled.epoch_fn, "_cache_size"):
        assert compiled.epoch_fn._cache_size() == 1
    np.testing.assert_allclose(
        np.asarray(r1.model), np.asarray(r2.model), rtol=1e-6
    )


def test_different_shape_is_a_cache_miss():
    eng = engine.Engine()
    d1 = synthetic.dense_classification(RNG, 128, 8)
    d2 = synthetic.dense_classification(RNG, 256, 8)
    for d in (d1, d2):
        eng.run(engine.AnalyticsQuery(task="svm", data=d,
                                      task_args={"dim": 8}, epochs=2,
                                      tolerance=0.0))
    assert eng.cache_info()["plan_cache_misses"] == 2


def test_forced_plans_execute_all_schemes():
    """Every physical scheme runs end-to-end through the executor."""
    data = synthetic.dense_classification(RNG, 128, 4)
    q = engine.AnalyticsQuery(task="logreg", data=data,
                              task_args={"dim": 4}, epochs=2, tolerance=0.0)
    eng = engine.Engine()
    plans = [
        engine.Plan("shuffle_once", "serial"),
        engine.Plan("shuffle_once", "segmented", num_segments=4),
        engine.Plan("clustered", "mrs", mrs_buffer=32),
    ]
    for p in plans:
        res = eng.run(q, plan=p)
        assert res.epochs == 2
        # stop-less queries evaluate the objective once, after the run
        assert len(res.losses) == 1
        assert np.isfinite(res.losses[-1]), p


# ---------------------------------------------------------------------------
# sweep driver (results/run_hillclimb* go through this)
# ---------------------------------------------------------------------------


def test_sweep_records_results_and_failures(tmp_path):
    from repro.engine import sweep as sweep_lib

    def fake_run(arch, shape, cfg_overrides=None, tag=None):
        if arch == "bad":
            raise RuntimeError("boom")
        return {"arch": arch, "shape": shape, "tag": tag, "status": "OK"}

    out = tmp_path / "log.jsonl"
    variants = [
        ("a1", "s", {}, None, "t1"),
        ("bad", "s", {}, None, "t2"),
        ("a2", "s", {}, None, "t3"),
    ]
    recs = sweep_lib.sweep(fake_run, variants, str(out), log_fn=lambda s: None)
    assert [r["status"] for r in recs] == ["OK", "FAIL", "OK"]
    assert len(out.read_text().strip().splitlines()) == 3
