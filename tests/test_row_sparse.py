"""The row-sparse IGD transition (``core.uda.IGDAggregate``): a task that
names the rows an example reads (``Task.example_rows``) is stepped on
those rows only, and every lane body the program compiler builds
inherits it. Each case runs the same plan over the same ratings with
``lmf`` and with a copy of it that names no rows (the dense step:
``jax.grad`` over the whole model, ``w - alpha * g`` over every
coordinate) and compares the factors.

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


def _close(a, b):
    for k in ("L", "R"):
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert np.all(np.isfinite(x))
        gap = np.max(np.abs(x - y)) / np.max(np.abs(y))
        assert gap <= 1e-6, (k, gap)


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
