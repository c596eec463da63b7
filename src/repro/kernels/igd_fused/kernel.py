"""Fused IGD transition kernel — the paper's hot loop on TPU.

Bismarck's transition is ``Dot_Product`` + scalar loss-gradient +
``Scale_And_Add`` per tuple, with the model hot in cache while tuples
stream from the buffer pool. The TPU adaptation (DESIGN.md §5):

* the model ``w`` crosses the grid's tiles in a [1, D] VMEM scratch
  buffer (initialized from HBM at grid step 0, written back at the last
  step) and lives in vregs, as the row loop's value, within a tile;
* examples stream HBM->VMEM in (TILE, D) blocks via the BlockSpec grid,
  and each tile's labels and step sizes stream into SMEM beside them;
* the strictly-sequential per-tuple dependence runs inside the kernel as a
  ``fori_loop`` over blocks of ``BLOCK`` rows, each statically unrolled
  row by row into VPU vector ops (D padded to 128): the block
  recurrence, below (its exact jnp oracle is ``ref.igd_fold_ref``).

Losses: "lr" (logistic), "svm" (hinge), "lsq" (least squares).

The block recurrence. Row i's step needs its margin against the model
that rows 0..i-1 of its block have already moved. One row at a time
that is a 128-lane reduction of ``w * x_i`` per row, each waiting on the
previous row's update: a latency chain, not a bandwidth bound. Within a
block of B rows the same margins follow from quantities the chain does
not wait on::

    x_i . (w - sum_{j<i} c_j x_j)  =  q_i - sum_{j<i} c_j G_ij,
    q = X_B w  (once per block),   G = X_B X_B^T  (from the rows alone)

so each block pays one batched reduction for its B margins (``q``), the
Gram columns are computed in float32 off the chain, and each row then
costs only its loss scale ``c_i`` and the [B, 1] update ``q -= c_i G[:, i]``.
The model itself is still updated row by row in row order,
``w -= c_i x_i``, exactly as the sequential fold does; only the
margins come from the block. That is what keeps the hinge's answers:
the margins move by rounding, the model's accumulation does not. On
Forest-shaped data (581,012 x 54, blocks of 8, plain ``jax.numpy`` on a
CPU), updating the model once per block as ``sum_j c_j x_j`` moved one
svm epoch's model by about 2% of its largest entry on each of 3 seeds:
a rounding-level margin change flips a hinge near margin 1, and one
flip moves the model. With the row-by-row update the svm model came out
bit for bit the plain sequential fold's, and 5 logreg epochs within
5e-7 of it.

Rounding alone still flips a hinge now and then: on a TPU v5e about one
one-epoch svm fit in 30 over the Forest table came out 1.7% away from
the sequential fold. So for svm each row's decision is also checked
against the margin of the row-at-a-time form, ``x_i . w`` summed across
the lanes. That reduction is off the chain: the next row does not wait
for it. ``w`` is the fold's own model for as long as every decision
agrees, so the check is exact; a tile with any disagreement is folded
again from its starting model, one row at a time. The logistic and least-squares steps are smooth in the margin and
need no check. Rows with ``alpha = 0`` (the tail padding) get ``c = 0``
exactly, so they leave both ``w`` and ``q`` untouched.

The engine reaches this kernel through the EpochProgram
``implementation`` axis: ``engine/program.py`` lowers serial lane
bodies onto ``ops.igd_fold`` for kernel-eligible tasks
(``catalog.kernel_loss_for``), and the planner prices the choice from a
micro-probe of the kernel lane (``Calibration.impl_per_row``) — see
ENGINE.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256  # examples per VMEM block
# rows per step of the serial kernel's block recurrence, four [8, 128]
# sublane tiles: on a TPU v5e at Forest's shape (logreg), blocks of 16
# rows took 8% longer per row and blocks of 64 only 4% less
BLOCK = 32


def _grad_scale(loss: str, margin, y):
    """d loss / d (w.x) given margin = y * (w.x) (lr/svm) or w.x (lsq)."""
    if loss == "lr":
        return -y * jax.nn.sigmoid(-margin)
    if loss == "svm":
        return jnp.where(margin < 1.0, -y, 0.0)
    if loss == "lsq":
        return margin - y  # here margin = w.x
    raise ValueError(loss)


def _igd_step(loss: str, w, xi, yi, ai, wx):
    """One transition given row i's margin ``wx`` ([1, 1]): the loss scale
    and the Scale_And_Add. The margin stays a vector, so sigmoid runs on
    the vector units; only y and alpha are scalars (SMEM reads)."""
    m = wx if loss == "lsq" else yi * wx
    c = _grad_scale(loss, m, yi) * ai
    return w - c * xi, c


def _igd_kernel(x_ref, y_ref, alpha_ref, w0_ref, wout_ref, wscr, *, loss: str,
                n_tiles: int):
    t = pl.program_id(0)
    # a hinge decision must be the sequential fold's: each is checked
    # against the margin the fold computes, and a tile with any other
    # decision is folded again row by row
    checked = loss == "svm"

    @pl.when(t == 0)
    def _init():
        wscr[...] = w0_ref[...]

    def block(b, carry):
        w, missed = carry
        r0 = pl.multiple_of(b * BLOCK, BLOCK)
        xb = x_ref[pl.ds(r0, BLOCK), :]  # [BLOCK, D]
        # the Gram columns G[:, i] = X_B x_i, which no model update waits on
        gram = [jnp.sum(xb * xb[i:i + 1, :], axis=1, keepdims=True)
                for i in range(BLOCK)]
        # every row's margin against the block's starting model at once
        q = jnp.sum(xb * w, axis=1, keepdims=True)  # [BLOCK, 1]
        for i in range(BLOCK):
            xi = xb[i:i + 1, :]  # [1, D]
            yi = y_ref[0, r0 + i]
            if checked:  # off the chain: nothing waits on it in this tile
                wx = jnp.sum(w * xi, axis=1, keepdims=True)
                missed = jnp.maximum(missed, jnp.where(
                    (yi * wx < 1.0) != (yi * q[i:i + 1, :] < 1.0), 1.0, 0.0))
            # Scale_And_Add, row by row
            w, c = _igd_step(loss, w, xi, yi, alpha_ref[0, r0 + i],
                             q[i:i + 1, :])
            q = q - c * gram[i]  # the later rows' margins against the new w
        return w, missed

    w_tile = wscr[...]
    wscr[...], missed = jax.lax.fori_loop(
        0, x_ref.shape[0] // BLOCK, block,
        (w_tile, jnp.zeros((1, 1), jnp.float32)))

    if checked:
        @pl.when(missed[0, 0] > 0.0)
        def _refold():
            def row(i, w):
                xi = x_ref[pl.ds(i, 1), :]
                return _igd_step(loss, w, xi, y_ref[0, i], alpha_ref[0, i],
                                 jnp.sum(w * xi, axis=1, keepdims=True))[0]

            wscr[...] = jax.lax.fori_loop(0, x_ref.shape[0], row, w_tile)

    @pl.when(t == n_tiles - 1)
    def _fin():
        wout_ref[...] = wscr[...]


def igd_fold(x, y, alpha, w0, *, loss: str = "lr", interpret: bool = False):
    """Sequential IGD over all n examples. x: [N, D] f32 (N % TILE == 0,
    D % 128 == 0), y/alpha: [N], w0: [D] -> final w [D]. The call is
    named ``igd_serial`` in HLO, and so in a device trace, whether it is
    jitted alone or vmapped.

    Layouts the TPU compiler accepts: the model is a [1, D] block (and a
    [1, D] VMEM scratch carried across the sequential grid), and y/alpha
    ride as [N / TILE, 1, TILE] in SMEM, so each grid step's block spans
    the array's two minor dims and each row reads its label and step as
    scalars."""
    n, d = x.shape
    assert n % TILE == 0, f"N={n} not a multiple of {TILE}"
    assert d % 128 == 0, f"D={d} not a multiple of 128"
    n_tiles = n // TILE
    row_spec = pl.BlockSpec(
        (None, 1, TILE), lambda t: (t, 0, 0), memory_space=pltpu.SMEM
    )
    model_spec = pl.BlockSpec((1, d), lambda t: (0, 0))
    out = pl.pallas_call(
        functools.partial(_igd_kernel, loss=loss, n_tiles=n_tiles),
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
        name="igd_serial",
    )(
        x,
        y.reshape(n_tiles, 1, TILE),
        alpha.reshape(n_tiles, 1, TILE),
        w0.reshape(1, d),
    )
    return out[0]
