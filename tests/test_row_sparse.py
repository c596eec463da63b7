"""The row-sparse IGD transition (``core.uda.IGDAggregate``): a task that
names the rows an example reads (``Task.example_rows``) is stepped on
those rows only, and every lane body the program compiler builds
inherits it. Each case runs the same plan over the same ratings with
``lmf`` and with a copy of it that names no rows (the dense step:
``jax.grad`` over the whole model, ``w - alpha * g`` over every
coordinate) and compares the factors.

The row kernel's cases (``kernels/igd_fused/rows.py``, in interpret
mode) compare it with ``uda.fold`` over the row-sparse transition, and
its vmapped lanes with singleton runs, within 1e-5 relative: its dot
sums the padded rank's lanes in another order.

Tolerance: one transition run op by op is bitwise equal (the same
gradient of the same loss, the same ``w - alpha * g``). Compiled, XLA
fuses the two bodies differently (the dense body's dot and update land
in other fusions than the row body's), so the rounding of a step can
differ by an ulp, and a fold carries those differences through the
remaining steps: compiled steps and folds are compared within 1e-6
relative per leaf, the largest gap over these cases being about 2e-7."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs
from repro.core import igd, uda
from repro.data import synthetic
from repro.engine import catalog, serve
from repro.tasks import LowRankMF

RNG = jax.random.PRNGKey(0)
N_ROWS, N_COLS, RANK = 48, 24, 8
ARGS = {"n_rows": N_ROWS, "n_cols": N_COLS, "rank": RANK, "mu": 1e-2,
        "alpha0": 0.02}


class DenseLMF(LowRankMF):
    """``lmf`` that names no rows: the transition takes the dense step."""

    example_rows = None


@pytest.fixture(scope="module", autouse=True)
def dense_lmf():
    spec = catalog.get("lmf")
    catalog.register_task(
        "lmf_dense", step_size=spec.step_size, derive_args=spec.derive_args,
        nonconvex=True,
    )(DenseLMF)
    yield
    catalog.unregister("lmf_dense")


def _ratings(n=512):
    d = synthetic.ratings(RNG, N_ROWS, N_COLS, n, rank=3)
    return dict(d, v=jnp.clip(jnp.round(3.6 + d["v"] / jnp.std(d["v"])),
                              1.0, 5.0))


def _query(task, data, seed=3, epochs=2):
    return engine.AnalyticsQuery(task=task, data=data, task_args=ARGS,
                                 epochs=epochs, tolerance=0.0, seed=seed)


def _close(a, b, tol=1e-6):
    for k in ("L", "R"):
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert np.all(np.isfinite(x))
        gap = np.max(np.abs(x - y)) / np.max(np.abs(y))
        assert gap <= tol, (k, gap)


def _aggs():
    task = LowRankMF(**{k: v for k, v in ARGS.items() if k != "alpha0"})
    step = igd.diminishing(0.02, decay=512)
    dense = DenseLMF(**dataclasses.asdict(task))
    return uda.IGDAggregate(task, step), uda.IGDAggregate(dense, step)


def test_one_row_step_is_the_dense_step():
    row, dense = _aggs()
    assert row.row_sparse and not dense.row_sparse
    data = _ratings()
    state = row.initialize(RNG)
    for k in (0, 7, 300):
        ex = jax.tree.map(lambda x, k=k: x[k], data)
        a = row.transition(state, ex)
        b = dense.transition(state, ex)
        for leaf in ("L", "R"):
            assert np.array_equal(np.asarray(a.model[leaf]),
                                  np.asarray(b.model[leaf]))
        _close(jax.jit(row.transition)(state, ex).model,
               jax.jit(dense.transition)(state, ex).model)
        # every row the example does not read is left bit for bit
        untouched = np.ones(N_ROWS, bool)
        untouched[int(ex["i"])] = False
        assert np.array_equal(np.asarray(a.model["L"])[untouched],
                              np.asarray(state.model["L"])[untouched])


def test_a_prox_keeps_the_dense_step():
    row, _ = _aggs()
    proxed = dataclasses.replace(row, prox=igd.make_l2_prox(1e-3))
    assert not proxed.row_sparse
    assert row.update_bytes() == 2 * RANK * 4
    assert proxed.update_bytes() == (N_ROWS + N_COLS) * RANK * 4


PLANS = {
    "singleton": engine.Plan("shuffle_always", "serial", unroll=4),
    "segmented": engine.Plan("clustered", "segmented", num_segments=4),
    "sharded": engine.Plan("shuffle_once", "serial", parallelism="sharded",
                           num_shards=2, merge_period=1, shard_devices=1),
}


@pytest.mark.parametrize("lane", sorted(PLANS))
def test_every_lane_body_steps_rows_as_the_dense_step(lane):
    data = _ratings()
    eng = engine.Engine()
    row = eng.run(_query("lmf", data), plan=PLANS[lane])
    dense = eng.run(_query("lmf_dense", data), plan=PLANS[lane])
    _close(row.model, dense.model)


def test_the_chunk_stream_steps_rows_as_the_dense_step():
    data = _ratings()
    tab = engine.ChunkedTable.from_arrays(data, 128)
    plan = engine.Plan("clustered", "serial", source="table")
    eng = engine.Engine()
    row = eng.run(_query("lmf", tab), plan=plan)
    dense = eng.run(_query("lmf_dense", tab), plan=plan)
    _close(row.model, dense.model)


def test_fused_serving_lanes_step_rows_as_the_dense_step():
    data = _ratings()
    hints = {"scheme": "serial", "ordering": "shuffle_always"}
    out = {}
    for task in ("lmf", "lmf_dense"):
        srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
        tickets = [srv.submit(dataclasses.replace(_query(task, data, seed=s),
                                                  hints=hints))
                   for s in (1, 2, 3)]
        srv.drain()
        assert srv.stats["batches"] == 1
        assert all(t.result.batch_size == 3 for t in tickets)
        out[task] = [t.result.model for t in tickets]
    for a, b in zip(out["lmf"], out["lmf_dense"]):
        _close(a, b)


def test_the_compiled_plan_records_the_bytes_one_step_writes():
    data = _ratings()
    engine.Engine().run(_query("lmf", data),
                        plan=PLANS["singleton"])
    gauge = obs.metrics.snapshot("program.")["program.update_bytes_per_row"]
    assert gauge["value"] == 2 * RANK * 4
    engine.Engine().run(_query("lmf_dense", data),
                        plan=PLANS["singleton"])
    gauge = obs.metrics.snapshot("program.")["program.update_bytes_per_row"]
    assert gauge["value"] == (N_ROWS + N_COLS) * RANK * 4


# ---------------------------------------------------------------------------
# the row kernel (kernels/igd_fused/rows.py): the lmf transition lowered
# to one pallas_call per epoch, in interpret mode here
# ---------------------------------------------------------------------------


def _kernel_ratings(n, n_rows, n_cols, seed=5):
    """Ratings sorted by user (runs of one user's ratings), with a movie
    id repeated on consecutive ratings inside a run and across the
    boundary between two users."""
    d = _ratings_at(n, n_rows, n_cols, seed)
    j = np.asarray(d["j"]).copy()
    i = np.asarray(d["i"])
    boundary = int(np.flatnonzero(np.diff(i))[0])  # last rating of a user
    j[boundary + 1] = j[boundary]
    j[5] = j[4]
    return dict(d, j=jnp.asarray(j))


def _ratings_at(n, n_rows, n_cols, seed):
    d = synthetic.ratings(jax.random.PRNGKey(seed), n_rows, n_cols, n,
                          rank=3)
    return dict(d, v=jnp.clip(jnp.round(3.6 + d["v"] / jnp.std(d["v"])),
                              1.0, 5.0))


def _lmf_agg(n_rows, n_cols, rank, n, prox=igd.identity_prox):
    task = LowRankMF(n_rows=n_rows, n_cols=n_cols, rank=rank, mu=1e-2,
                     **LowRankMF.degrees_for(n_rows, n_cols, n))
    return uda.IGDAggregate(task, igd.diminishing(0.02, decay=n), prox=prox)


@pytest.mark.parametrize("rank", [8, 50, 130])
def test_row_kernel_folds_as_the_row_sparse_transition(rank):
    """700 ratings (not a multiple of the kernel's tile of 512): the
    kernel lane agrees with ``uda.fold`` over the row-sparse transition
    within 1e-5 relative per table, and advances step and weight as it."""
    from repro.engine import program

    n, n_rows, n_cols = 700, 40, 24
    data = _kernel_ratings(n, n_rows, n_cols)
    agg = _lmf_agg(n_rows, n_cols, rank, n)
    state = agg.initialize(RNG)
    state = uda.IGDState(state.model, jnp.int32(n), jnp.float32(n))
    ref = jax.jit(lambda s, ex: uda.fold(agg, s, ex))(state, data)
    out = jax.jit(program.kernel_lane_fold(agg, "lmf"))(state, data)
    assert int(out.step) == int(ref.step) == 2 * n
    assert float(out.weight) == float(ref.weight)
    for k in ("L", "R"):
        x, y = np.asarray(out.model[k]), np.asarray(ref.model[k])
        assert x.shape == y.shape == np.asarray(state.model[k]).shape
        assert np.all(np.isfinite(x))
        gap = np.max(np.abs(x - y)) / np.max(np.abs(y))
        assert gap <= 1e-5, (k, gap)


def test_row_kernel_matches_its_oracle():
    from repro.kernels.igd_fused import ops, ref

    n, n_rows, n_cols, rank = 600, 32, 16, 50
    data = _kernel_ratings(n, n_rows, n_cols, seed=7)
    model = _lmf_agg(n_rows, n_cols, rank, n).initialize(RNG).model
    alpha = 0.02 / (1.0 + jnp.arange(n, dtype=jnp.float32) / n)
    args = (data["i"], data["j"], data["v"], alpha, model["L"], model["R"])
    scales = {"c_row": 1e-3, "c_col": 3e-3}
    for x, y in zip(ops.lmf_fold(*args, **scales),
                    ref.lmf_fold_ref(*args, **scales)):
        x, y = np.asarray(x), np.asarray(y)
        assert np.max(np.abs(x - y)) / np.max(np.abs(y)) <= 1e-5


@pytest.mark.parametrize("rank,n_rows,n_cols", [
    (1, 7, 5), (128, 33, 17), (129, 40, 24),
])
@pytest.mark.parametrize("n", [1, 511, 513, 1027])
def test_row_kernel_padding_matrix(n, rank, n_rows, n_cols):
    """The padding matrix of the row kernel against its oracle: a single
    rating, one short of and one past the kernel's tile (and two tiles
    and three ratings), a rank of one lane, of exactly one lane multiple
    and one past it, and tables whose row counts are not multiples of 8.
    Within 1e-5 relative per table (the dot sums the padded lanes in
    another order)."""
    from repro.kernels.igd_fused import ops, ref, rows

    assert (511, 513, 1027) == (rows.TILE - 1, rows.TILE + 1,
                                2 * rows.TILE + 3)
    data = _ratings_at(n, n_rows, n_cols, seed=11)
    keys = jax.random.split(jax.random.PRNGKey(n), 2)
    left = 0.3 * jax.random.normal(keys[0], (n_rows, rank), jnp.float32)
    right = 0.3 * jax.random.normal(keys[1], (n_cols, rank), jnp.float32)
    alpha = 0.02 / (1.0 + jnp.arange(n, dtype=jnp.float32) / n)
    args = (data["i"], data["j"], data["v"], alpha, left, right)
    scales = {"c_row": 1e-3, "c_col": 3e-3}
    for x, y, t in zip(ops.lmf_fold(*args, **scales),
                       ref.lmf_fold_ref(*args, **scales), (left, right)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape == t.shape
        assert np.all(np.isfinite(x))
        assert np.max(np.abs(x - y)) / np.max(np.abs(y)) <= 1e-5


def test_row_kernel_zero_alpha_ratings_are_bitwise_noops():
    """The padding the wrapper adds carries alpha = 0: such ratings write
    back the rows they read, bit for bit, and the padded rank lanes stay
    zero."""
    from repro.kernels.igd_fused import ops, rows

    n, n_rows, n_cols, rank = 300, 16, 8, 50
    data = _kernel_ratings(n, n_rows, n_cols)
    model = _lmf_agg(n_rows, n_cols, rank, n).initialize(RNG).model
    left, right = ops.lmf_fold(data["i"], data["j"], data["v"],
                               jnp.zeros((n,), jnp.float32),
                               model["L"], model["R"], c_row=1e-3,
                               c_col=1e-3)
    assert np.array_equal(np.asarray(left), np.asarray(model["L"]))
    assert np.array_equal(np.asarray(right), np.asarray(model["R"]))
    # the raw kernel over the padded layout: live ratings, then a
    # zero-alpha tail; the rank lanes past 50 stay exactly zero
    pad = rows.TILE - n
    cols = [jnp.pad(c, (0, pad)) for c in (data["i"], data["j"], data["v"])]
    alpha = jnp.pad(jnp.full((n,), 0.02, jnp.float32), (0, pad))
    tables = [jnp.pad(t, ((0, 0), (0, 128 - rank)))
              for t in (model["L"], model["R"])]
    out = rows.lmf_fold(*cols, alpha, *tables, c_row=1e-3, c_col=1e-3,
                        interpret=True)
    for t in out:
        assert not np.any(np.asarray(t)[:, rank:])
    live = ops.lmf_fold(data["i"], data["j"], data["v"], alpha[:n],
                        model["L"], model["R"], c_row=1e-3, c_col=1e-3)
    for t, u in zip(out, live):
        assert np.array_equal(np.asarray(t)[:, :rank], np.asarray(u))


def test_kernel_eligibility_of_the_row_kernel():
    from repro.engine import program
    from repro.kernels.igd_fused import rows

    agg = _lmf_agg(6040, 3706, 50, 1_000_209)
    assert program.kernel_eligibility(agg.task, agg) == ("lmf", "")
    assert rows.table_bytes(6040, 3706, 50) == (6040 + 3712) * 128 * 4

    dense_task = DenseLMF(**dataclasses.asdict(agg.task))
    dense = uda.IGDAggregate(dense_task, agg.step_size)
    loss, why = program.kernel_eligibility(dense_task, dense)
    assert loss is None and "kernel_loss" in why

    proxed = _lmf_agg(6040, 3706, 50, 1_000_209, prox=igd.make_l2_prox(1e-3))
    loss, why = program.kernel_eligibility(proxed.task, proxed)
    assert loss is None and "prox" in why

    # 24,576 padded rows of 128 lanes are the budget exactly
    fits = _lmf_agg(12_288, 12_288, 128, 1000)
    assert rows.table_bytes(12_288, 12_288, 128) == rows.VMEM_TABLE_BUDGET
    assert program.kernel_eligibility(fits.task, fits)[0] == "lmf"
    big = _lmf_agg(12_288, 12_289, 128, 1000)
    loss, why = program.kernel_eligibility(big.task, big)
    assert loss is None and "VMEM" in why
    with pytest.raises(ValueError, match="kernel-eligible"):
        program.require_kernel_loss(big.task, big, "pallas_fused")
    # pallas_fused is the one kernel lowering: any other name is refused
    with pytest.raises(ValueError, match="unknown implementation"):
        program.build_epoch_fn(agg.task, agg, engine.Plan(
            "clustered", "serial", implementation="pallas_minibatch"))


def _seeded_plan(query, **rates):
    """The planner's report for ``query`` under a calibration with the
    given measured rates (seconds/row), installed as if probed."""
    from repro.engine import planner, probes

    eng = engine.Engine()
    _, _, agg = eng._aggregate_for(query)
    key = query.cache_key_fields()
    probes.seed(key, probes.Calibration(
        shuffle_per_row=rates["shuffle"],
        fold_per_row={1: rates["fold"], 8: rates["fold"]},
        merge_seconds=1e-5, probe_rows=256,
        seg_per_row={8: rates["fold"]},
        impl_per_row=rates["impl"],
    ))
    try:
        return planner.plan(query, agg)
    finally:
        probes._CACHE.pop(key, None)


@pytest.mark.parametrize("kernel_rate,chosen", [
    (1e-7, "pallas_fused"), (1e-5, "xla_fold"),
])
def test_the_planner_prices_the_row_kernel(kernel_rate, chosen):
    """lmf's kernel lane is enumerated beside the scan for serial
    singleton plans and chosen only where the probe prices it lower."""
    data = _ratings()
    rep = _seeded_plan(_query("lmf", data), shuffle=4.5e-7, fold=3.4e-6,
                       impl={"pallas_fused": kernel_rate})
    impls = {c.plan.implementation for c in rep.candidates}
    assert impls == {"xla_fold", "pallas_fused"}
    assert rep.chosen.implementation == chosen
    assert rep.chosen.scheme == "serial"
    assert rep.chosen.parallelism == "singleton"


def test_the_row_kernel_probe_times_one_lane():
    from repro.engine import probes

    data = _ratings()
    _, _, agg = engine.Engine()._aggregate_for(_query("lmf", data))
    rates = probes._probe_implementations(
        agg, data, agg.initialize(RNG), data["i"].shape[0])
    assert set(rates) == {"pallas_fused"} and rates["pallas_fused"] > 0
    # a table of other columns is not the kernel's rows
    assert probes._probe_implementations(
        agg, dict(data, w=data["v"]), agg.initialize(RNG), 512) == {}


def test_a_forest_shaped_query_keeps_its_probes_and_plan():
    """Dense ``{x, y}`` rows still probe the dense kernel lane, and at
    rates like the Forest cell's (clustered by label) the planner still
    picks the shuffle_once kernel plan."""
    from repro.engine import probes

    raw = synthetic.dense_classification(RNG, 512, 54)
    order = jnp.argsort(raw["y"])
    data = jax.tree.map(lambda x: x[order], raw)
    query = engine.AnalyticsQuery(task="logreg", data=data,
                                  task_args={"dim": 54}, epochs=5,
                                  tolerance=0.0, seed=1)
    _, _, agg = engine.Engine()._aggregate_for(query)
    rates = probes._probe_implementations(agg, data, agg.initialize(RNG), 512)
    assert set(rates) == {"pallas_fused"}
    rep = _seeded_plan(query, shuffle=4.6e-8, fold=1e-6,
                       impl={"pallas_fused": 3.3e-8})
    chosen = rep.chosen
    assert (chosen.ordering, chosen.scheme, chosen.parallelism,
            chosen.implementation) == (
        "shuffle_once", "serial", "singleton", "pallas_fused"), chosen


@pytest.mark.parametrize("ordering", ["clustered", "shuffle_always"])
def test_fused_serving_lanes_run_the_row_kernel_lane_by_lane(ordering):
    """A forced pallas_fused hint in a fused serving batch vmaps the row
    kernel's lane, which runs the kernel once per query lane: each lane
    equals its own singleton kernel run."""
    data = _ratings()
    hints = {"ordering": ordering, "scheme": "serial",
             "implementation": "pallas_fused"}
    budgets = ((1, 2), (2, 1), (3, 2))
    eng = engine.Engine()
    singles = [eng.run(dataclasses.replace(_query("lmf", data, s, e),
                                           hints=hints))
               for s, e in budgets]
    srv = serve.ServingEngine(serve.ServeConfig(max_batch=4))
    tickets = [srv.submit(dataclasses.replace(_query("lmf", data, s, e),
                                              hints=hints))
               for s, e in budgets]
    srv.drain()
    assert srv.stats["batches"] == 1
    for t, ref in zip(tickets, singles):
        assert t.error is None, t.error
        assert t.result.plan.implementation == "pallas_fused"
        _close(t.result.model, ref.model, tol=1e-5)


def test_sharded_lanes_run_the_row_kernel():
    """A forced pallas_fused hint on a sharded plan lowers each shard
    lane's fold through the row kernel and agrees with the scan's lanes."""
    data = _ratings()
    hints = {"parallelism": "sharded", "num_shards": 2, "merge_period": 1}
    eng = engine.Engine()
    ref = eng.run(dataclasses.replace(
        _query("lmf", data), hints=dict(hints, implementation="xla_fold")))
    res = eng.run(dataclasses.replace(
        _query("lmf", data), hints=dict(hints, implementation="pallas_fused")))
    assert res.plan.parallelism == "sharded"
    assert res.plan.implementation == "pallas_fused"
    _close(res.model, ref.model, tol=1e-5)
