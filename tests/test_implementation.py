"""The EpochProgram implementation axis (xla_fold | pallas_fused).

Pins the contract from both directions: ``implementation=xla_fold`` is
bit-identical to the default lane bodies (the axis is a pure addition),
``pallas_fused`` lanes agree with the XLA fold within fp32 fold
tolerance on every driver (singleton, chunk stream, sharded, fused
serving batch), the planner's choice is probe-priced (EXPLAIN's why
line carries measured us/epoch per implementation), and ineligible or
contradictory hints fail loudly instead of silently falling back.
"""

import jax
import numpy as np
import pytest

from repro import engine
from repro.data import synthetic
from repro.engine import serve

RNG = jax.random.PRNGKey(0)

ORDERINGS = ("clustered", "shuffle_once", "shuffle_always")


def _q(data, seed=0, epochs=3, task="logreg", **kw):
    kw.setdefault("tolerance", 0.0)
    return engine.AnalyticsQuery(
        task=task, data=data, task_args={"dim": 4}, seed=seed,
        epochs=epochs, **kw
    )


def _data(n=96):
    return synthetic.dense_classification(RNG, n, 4)


# ---------------------------------------------------------------------------
# xla_fold is the identity: forcing it changes nothing, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ordering", ORDERINGS)
def test_xla_fold_hint_bit_identical_to_default(ordering):
    """The axis is additive: an explicit implementation=xla_fold hint
    must reproduce the unhinted plan's floats exactly, per ordering."""
    data = _data()
    eng = engine.Engine()
    base = {"ordering": ordering, "scheme": "serial"}
    ref = eng.run(_q(data, hints=dict(base)))
    forced = eng.run(
        _q(data, hints=dict(base, implementation="xla_fold"))
    )
    assert forced.plan.implementation == "xla_fold"
    assert np.array_equal(np.asarray(forced.model), np.asarray(ref.model))
    assert forced.losses == ref.losses


# ---------------------------------------------------------------------------
# pallas_fused parity vs the XLA oracle, across drivers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ordering", ORDERINGS)
def test_pallas_fused_matches_xla_oracle(ordering):
    """Forced kernel lanes replay the exact sequential IGD recurrence:
    same rows, same alpha schedule, same step/weight accounting — only
    the arithmetic is re-associated, so fp32 fold tolerance."""
    data = _data()
    eng = engine.Engine()
    base = {"ordering": ordering, "scheme": "serial"}
    ref = eng.run(_q(data, hints=dict(base, implementation="xla_fold")))
    res = eng.run(
        _q(data, hints=dict(base, implementation="pallas_fused"))
    )
    assert res.plan.implementation == "pallas_fused"
    assert res.epochs == ref.epochs
    np.testing.assert_allclose(
        np.asarray(res.model), np.asarray(ref.model),
        rtol=1e-5, atol=1e-6, err_msg=ordering,
    )


def test_chunk_stream_pallas_matches_xla():
    """The stored-table chunk stream lowers through the same kernel
    lane; alphas continue from state.step across chunk boundaries."""
    data = _data()
    tab = engine.ChunkedTable.from_arrays(data, 32)
    eng = engine.Engine()
    ref = eng.run(_q(tab, hints={"source": "table",
                                 "implementation": "xla_fold"}))
    res = eng.run(_q(tab, hints={"source": "table",
                                 "implementation": "pallas_fused"}))
    assert res.plan.source == "table"
    assert res.plan.implementation == "pallas_fused"
    np.testing.assert_allclose(
        np.asarray(res.model), np.asarray(ref.model),
        rtol=1e-5, atol=1e-6,
    )


def test_sharded_pallas_matches_xla():
    """Shard-block lane bodies lower too; the merge tree sees the same
    per-lane step/weight accounting, so weighted averaging agrees."""
    data = _data()
    eng = engine.Engine()
    hints = {"parallelism": "sharded", "num_shards": 2, "merge_period": 2}
    ref = eng.run(_q(data, hints=dict(hints, implementation="xla_fold")))
    res = eng.run(
        _q(data, hints=dict(hints, implementation="pallas_fused"))
    )
    assert res.plan.parallelism == "sharded"
    assert res.plan.implementation == "pallas_fused"
    np.testing.assert_allclose(
        np.asarray(res.model), np.asarray(ref.model),
        rtol=1e-5, atol=1e-6,
    )


def test_serve_fused_batch_pallas_matches_singleton():
    """Heterogeneous-epoch fused batches vmap the kernel lane; each lane
    must still equal its own singleton pallas run."""
    data = _data()
    hints = {"ordering": "shuffle_always", "scheme": "serial",
             "implementation": "pallas_fused"}
    budgets = (4, 2, 4)
    eng = engine.Engine()
    singles = [
        eng.run(_q(data, seed=s, epochs=e, hints=dict(hints)))
        for s, e in enumerate(budgets)
    ]
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    tickets = [
        srv.submit(_q(data, seed=s, epochs=e, hints=dict(hints)))
        for s, e in enumerate(budgets)
    ]
    srv.drain()
    assert srv.stats["batches"] == 1
    for t, ref in zip(tickets, singles):
        assert t.error is None, t.error
        np.testing.assert_allclose(
            np.asarray(t.result.model), np.asarray(ref.model),
            rtol=1e-5, atol=1e-7,
        )


# ---------------------------------------------------------------------------
# planner: probe-priced choice, EXPLAIN surfacing
# ---------------------------------------------------------------------------


def test_planner_prices_implementations_from_probes():
    """The implementation choice is measured, not assumed: calibration
    carries per-row kernel rates probed on the same slab as the XLA
    fold, the planner enumerates pallas candidates, and EXPLAIN's why
    line shows the measured us/epoch for every implementation."""
    rep = engine.explain(_q(_data()))
    rates = rep.calibration.impl_per_row
    assert set(rates) == {"pallas_fused"} and rates["pallas_fused"] > 0.0
    assert any(
        c.plan.implementation == "pallas_fused" for c in rep.candidates
    )
    text = rep.describe()
    assert "impl-probed" in text
    assert "pallas_fused" in text and "us/epoch" in text


def test_axes_line_names_the_implementation():
    """EXPLAIN's composed-axes rendering includes the fifth axis."""
    data = _data()
    eng = engine.Engine()
    rep = eng.explain(_q(data))
    assert "implementation=xla_fold" in rep.axes
    forced = eng.explain(
        _q(data, hints={"implementation": "pallas_fused"})
    )
    assert forced.chosen.implementation == "pallas_fused"
    assert "implementation=pallas_fused" in forced.chosen.axes()


def test_explain_analyze_prices_lane_body_on_the_impl_row():
    """EXPLAIN ANALYZE decomposes serial-singleton compute onto the
    implementation axis: the row carries both the prediction and the
    measured epoch wall, and parallelism's measured side is zero (the
    axes split the same total, they don't double-count)."""
    rep = engine.Engine().explain_analyze(
        _q(_data(), hints={"implementation": "pallas_fused"})
    )
    rows = {r.axis: r for r in rep.rows}
    assert set(rows) == {
        "ordering", "parallelism", "batching", "source", "implementation"
    }
    assert rows["implementation"].predicted_s > 0.0
    assert rows["implementation"].measured_s > 0.0
    assert rows["parallelism"].measured_s == 0.0
    assert "pallas_fused" in rows["implementation"].detail


# ---------------------------------------------------------------------------
# hints fail loudly
# ---------------------------------------------------------------------------


def test_forced_kernel_on_ineligible_task_raises():
    """logreg with mu > 0 routes through the l1 prox — the fused kernel
    has no prox hook, so the hint must be rejected, not ignored."""
    data = _data()
    q = engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 4, "mu": 0.01},
        epochs=3, tolerance=0.0,
        hints={"implementation": "pallas_fused"},
    )
    with pytest.raises(ValueError, match="kernel-eligible"):
        engine.explain(q)


def test_forced_kernel_conflicts_with_nonserial_scheme():
    with pytest.raises(ValueError):
        engine.explain(_q(_data(), hints={
            "implementation": "pallas_fused", "scheme": "mrs",
        }))


def test_unknown_implementation_hint_raises():
    """The axes name two lowerings and three singleton schemes; any other
    name is refused, the removed minibatch kernel and shared-memory
    simulator among them."""
    with pytest.raises(ValueError):
        engine.explain(_q(_data(), hints={"implementation": "cuda"}))
    with pytest.raises(ValueError, match="unknown implementation hint"):
        engine.explain(_q(_data(),
                          hints={"implementation": "pallas_minibatch"}))
    with pytest.raises(ValueError, match="unknown scheme hint"):
        engine.explain(_q(_data(), hints={"scheme": "shared_memory"}))
