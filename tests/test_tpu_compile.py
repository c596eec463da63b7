"""Ahead-of-time compiles of the fused-IGD kernels for a described TPU v5e.

Interpret mode accepts layouts that the chip's compiler refuses (1-D
VMEM blocks read at dynamic indices, 1-D operands of an MXU product), so
these tests lower the kernels at the Forest covertype shape (581,012 rows
x 54 features) for a v5e that is described, not attached. Nothing runs:
a pass says the chip's compiler accepts the kernels and that the
programs fit one chip's memory, not that they are fast or correct.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers each import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import uda
from repro.engine import catalog, program
from repro.kernels.igd_fused import ops as igd_ops

FOREST_ROWS, FOREST_DIM = 581_012, 54
BATCH = 8
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table_specs(sharding, n=FOREST_ROWS, d=FOREST_DIM):
    return (_spec(sharding, (n, d)), _spec(sharding, (n,)),
            _spec(sharding, (n,)), _spec(sharding, (d,)))


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


@pytest.mark.parametrize("loss", ["lr", "svm", "lsq"])
def test_igd_fold_compiles_for_v5e(one_chip, loss):
    fold = jax.jit(lambda x, y, a, w: igd_ops.igd_fold(
        x, y, a, w, loss=loss, interpret=False))
    _assert_kernel_fits(fold.lower(*_table_specs(one_chip)).compile())


@pytest.mark.parametrize("ordering", ["clustered", "shuffle_always"])
def test_fused_kernel_lane_compiles_for_v5e(one_chip, ordering):
    """The fused serving batch's lane body (``program._build_fused``):
    B=8 query lanes vmapped over the kernel lane, sharing one table.
    The stored order vmaps the plain lane; shuffles vmap the lane behind
    each query's permutation."""
    spec = catalog.get("logreg")
    task = spec.make_task(dim=FOREST_DIM)
    agg = uda.IGDAggregate(task, spec.step_size(FOREST_ROWS),
                           prox=spec.prox(task))
    states = uda.IGDState(
        _spec(one_chip, (BATCH, FOREST_DIM)),
        _spec(one_chip, (BATCH,), jnp.int32),
        _spec(one_chip, (BATCH,)),
    )
    x, y, _, _ = _table_specs(one_chip)
    data = {"x": x, "y": y}
    if ordering == "clustered":
        lane = jax.vmap(
            program.kernel_lane_fold(agg, "lr", interpret=False),
            in_axes=(0, None),
        )
        args = (states, data)
    else:
        lane = jax.vmap(
            program.kernel_permuted_lane(agg, "lr", interpret=False),
            in_axes=(0, None, 0),
        )
        args = (states, data,
                _spec(one_chip, (BATCH, FOREST_ROWS), jnp.int32))
    _assert_kernel_fits(jax.jit(lane).lower(*args).compile())


# MovieLens-1M at the benchmark's rank: the row kernel's tables
ML_USERS, ML_MOVIES, ML_RATINGS, ML_RANK = 6040, 3706, 1_000_209, 50


def _row_fold(i, j, v, a, left, right):
    return igd_ops.lmf_fold(i, j, v, a, left, right, c_row=1e-4,
                            c_col=1e-4, interpret=False)


def _rating_specs(sharding, n):
    return (_spec(sharding, (n,), jnp.int32), _spec(sharding, (n,), jnp.int32),
            _spec(sharding, (n,)), _spec(sharding, (n,)))


# the dense fold's cases go by its kernel's name, the lmf row fold's by
# its technique: both kernels are named igd_serial
@pytest.mark.parametrize("fold,lanes", [
    ("igd_serial", 0), ("igd_serial", 2), ("lmf", 0), ("lmf", 2),
])
def test_the_kernel_is_named_in_the_compiled_program(one_chip, fold, lanes):
    """A profiler names a device operation after its HLO instruction, so
    the pallas_call's ``name=`` is what a trace shows: the same stem for
    the kernel alone and vmapped over query lanes. The lmf row kernel
    carries the dense kernel's name, ``igd_serial``, which is how
    ``kernel_roofline.lmf`` finds it in a trace."""
    if fold == "lmf":
        lane = _row_fold
        stack = (lanes,) if lanes else ()
        if lanes:
            lane = jax.vmap(lane, in_axes=(None,) * 4 + (0, 0))
        args = (*_rating_specs(one_chip, 4096),
                _spec(one_chip, stack + (40, ML_RANK)),
                _spec(one_chip, stack + (24, ML_RANK)))
    else:
        def lane(x, y, a, w):
            return igd_ops.igd_fold(x, y, a, w, loss="lr", interpret=False)

        x, y, a, w = _table_specs(one_chip, n=4096)
        if lanes:
            lane = jax.vmap(lane, in_axes=(None, None, None, 0))
            w = _spec(one_chip, (lanes, FOREST_DIM))
        args = (x, y, a, w)
    text = jax.jit(lane).lower(*args).compile().as_text()
    assert "%igd_serial." in text


@pytest.mark.parametrize("lanes", [0, 2])
def test_row_kernel_compiles_for_v5e(one_chip, lanes):
    """The lmf row kernel at the MovieLens-1M shape, both factor tables
    held in VMEM, alone and vmapped over query lanes (which run the
    kernel lane by lane); named like the dense serial kernel."""
    fold = _row_fold
    tables = ((ML_USERS, ML_RANK), (ML_MOVIES, ML_RANK))
    if lanes:
        fold = jax.vmap(fold, in_axes=(None,) * 4 + (0, 0))
        tables = tuple((lanes,) + t for t in tables)
    compiled = jax.jit(fold).lower(
        *_rating_specs(one_chip, ML_RATINGS),
        *(_spec(one_chip, t) for t in tables),
    ).compile()
    _assert_kernel_fits(compiled)
    assert "%igd_serial." in compiled.as_text()


def test_row_kernel_compiles_at_its_vmem_budget(one_chip):
    """Tables that take the whole budget (``rows.VMEM_TABLE_BUDGET``) still
    compile: the budget is one the chip's compiler accepts."""
    from repro.kernels.igd_fused import rows

    side = rows.VMEM_TABLE_BUDGET // (2 * 128 * 4)
    assert rows.table_bytes(side, side, 128) == rows.VMEM_TABLE_BUDGET
    compiled = jax.jit(_row_fold).lower(
        *_rating_specs(one_chip, 4096),
        _spec(one_chip, (side, 128)), _spec(one_chip, (side, 128)),
    ).compile()
    _assert_kernel_fits(compiled)
