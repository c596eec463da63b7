"""Fused IGD transition kernel — the paper's hot loop on TPU.

Bismarck's transition is ``Dot_Product`` + scalar loss-gradient +
``Scale_And_Add`` per tuple, with the model hot in cache while tuples
stream from the buffer pool. The TPU adaptation (DESIGN.md §5):

* the model ``w`` lives in a [1, D] VMEM scratch buffer for the whole
  aggregate (initialized from HBM at grid step 0, written back at the
  last step);
* examples stream HBM->VMEM in (TILE, D) blocks via the BlockSpec grid,
  and each tile's labels and step sizes stream into SMEM beside them;
* the strictly-sequential per-tuple dependence runs inside the kernel as a
  ``fori_loop`` of VPU vector ops over [1, D] rows (D padded to 128);
* a ``minibatch`` variant instead computes the whole tile's margins with
  one MXU matvec and applies the summed update — trading IGD purity for
  MXU utilization (both have exact jnp oracles in ref.py).

Losses: "lr" (logistic), "svm" (hinge), "lsq" (least squares).

The engine reaches these kernels through the EpochProgram
``implementation`` axis: ``engine/program.py`` lowers serial lane
bodies onto ``ops.igd_fold`` / ``ops.igd_fold_minibatch`` for
kernel-eligible tasks (``catalog.kernel_loss_for``), and the planner
prices the choice from per-implementation micro-probes
(``Calibration.impl_per_row``) — see ENGINE.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256  # examples per VMEM block (and per minibatch step)


def _grad_scale(loss: str, margin, y):
    """d loss / d (w.x) given margin = y * (w.x) (lr/svm) or w.x (lsq)."""
    if loss == "lr":
        return -y * jax.nn.sigmoid(-margin)
    if loss == "svm":
        return jnp.where(margin < 1.0, -y, 0.0)
    if loss == "lsq":
        return margin - y  # here margin = w.x
    raise ValueError(loss)


def _igd_kernel(x_ref, y_ref, alpha_ref, w0_ref, wout_ref, wscr, *, loss: str,
                n_tiles: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        wscr[...] = w0_ref[...]

    def body(i, _):
        xi = x_ref[pl.ds(i, 1), :]  # [1, D]
        w = wscr[...]
        # the margin stays a [1, 1] vector: sigmoid runs on the vector
        # units, and only y and alpha are scalars (SMEM reads)
        wx = jnp.sum(w * xi, axis=1, keepdims=True)
        yi = y_ref[0, i]
        m = wx if loss == "lsq" else yi * wx
        c = _grad_scale(loss, m, yi) * alpha_ref[0, i]
        wscr[...] = w - c * xi  # Scale_And_Add
        return 0

    jax.lax.fori_loop(0, x_ref.shape[0], body, 0)

    @pl.when(t == n_tiles - 1)
    def _fin():
        wout_ref[...] = wscr[...]


def _minibatch_kernel(x_ref, y_ref, alpha_ref, w0_ref, wout_ref, wscr, *,
                      loss: str, n_tiles: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        wscr[...] = w0_ref[...]

    w = wscr[...]  # [1, D]
    x = x_ref[...]  # [TILE, D]
    # [1, D] x [TILE, D]^T -> [1, TILE]: one MXU pass for the tile's margins
    wx = jax.lax.dot_general(
        w, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = y_ref[...]  # [1, TILE]
    m = wx if loss == "lsq" else y * wx
    c = _grad_scale(loss, m, y) * alpha_ref[...]
    upd = jnp.dot(c, x, preferred_element_type=jnp.float32)  # [1, D]
    wscr[...] = w - upd / x_ref.shape[0]

    @pl.when(t == n_tiles - 1)
    def _fin():
        wout_ref[...] = wscr[...]


def _fold_call(kernel, x, y, alpha, w0, *, loss: str, y_alpha_space,
               interpret: bool):
    """Shared pallas_call plumbing. x: [N, D] f32 (N % TILE == 0,
    D % 128 == 0), y/alpha: [N], w0: [D] -> final w [D].

    Layouts the TPU compiler accepts: the model is a [1, D] block (and a
    [1, D] VMEM scratch carried across the sequential grid), and y/alpha
    ride as [N / TILE, 1, TILE] so each grid step's block spans the
    array's two minor dims, in ``y_alpha_space`` (SMEM for per-row
    scalar reads, None for VMEM vectors)."""
    n, d = x.shape
    assert n % TILE == 0, f"N={n} not a multiple of {TILE}"
    assert d % 128 == 0, f"D={d} not a multiple of 128"
    n_tiles = n // TILE
    row_spec = pl.BlockSpec(
        (None, 1, TILE), lambda t: (t, 0, 0), memory_space=y_alpha_space
    )
    model_spec = pl.BlockSpec((1, d), lambda t: (0, 0))
    out = pl.pallas_call(
        functools.partial(kernel, loss=loss, n_tiles=n_tiles),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((TILE, d), lambda t: (t, 0)),
            row_spec,
            row_spec,
            model_spec,
        ],
        out_specs=model_spec,
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(
        x,
        y.reshape(n_tiles, 1, TILE),
        alpha.reshape(n_tiles, 1, TILE),
        w0.reshape(1, d),
    )
    return out[0]


def igd_fold(x, y, alpha, w0, *, loss: str = "lr", interpret: bool = False):
    """Sequential IGD over all n examples; y and alpha are read as
    scalars from SMEM, one per row."""
    return _fold_call(_igd_kernel, x, y, alpha, w0, loss=loss,
                      y_alpha_space=pltpu.SMEM, interpret=interpret)


def igd_fold_minibatch(x, y, alpha, w0, *, loss: str = "lr",
                       interpret: bool = False):
    """Minibatch variant: one gradient step per TILE (mean gradient),
    margins computed with an MXU matmul; y and alpha are [1, TILE]
    vectors in VMEM."""
    return _fold_call(_minibatch_kernel, x, y, alpha, w0, loss=loss,
                      y_alpha_space=None, interpret=interpret)
