"""Row-sparse IGD kernel for low-rank factorization (``lmf``).

One ``pallas_call`` folds a whole epoch of ratings ``{i, j, v}`` through
the row-sparse transition of ``core.uda.IGDAggregate``: rating k reads
row ``i_k`` of the user factors L and row ``j_k`` of the movie factors
R, takes the gradient of ``LowRankMF.example_loss`` on those two rows
and writes back only them, in rating order.

The TPU adaptation:

* both factor tables stay in VMEM for the whole epoch: they arrive in
  HBM (``memory_space=ANY``), are copied into one VMEM scratch each at
  the first grid step and written back at the last one, into outputs
  aliased to the inputs, so VMEM holds one copy of each and nothing is
  double-buffered;
* ratings stream along a sequential grid in tiles of ``TILE``; each
  tile's ids, stars and step sizes arrive in SMEM blocks, one scalar
  read per rating;
* each rating loads its two rows at dynamic sublane offsets
  (``L[pl.ds(i, 1), :]``), computes the error as a float32 lane
  reduction and stores the two updated rows. A later rating that reads a
  row an earlier one wrote (the next rating of the same user, or a movie
  seen again) loads it after that store: the fold is the sequential one.

The gradient is ``jax.grad`` of ``example_loss`` in the form it takes on
one-row slices (``uda._row_step``), with ``e = l . r - v`` and
``c = mu / degree``::

    l' = l - alpha * ((c_row * l + c_row * l) + 2e * r)
    r' = r - alpha * ((c_col * r + c_col * r) + 2e * l)

The callers pad the rank to a multiple of 128 lanes (zero lanes stay
zero and add zeros to the dot, so the dot's order of additions is the
one thing that changes) and the ratings to a multiple of ``TILE`` with
``alpha = 0``, which makes a padded rating write back the rows it read,
bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 512  # ratings per grid step (one SMEM block of each column)
# ratings per iteration of the in-tile loop: on a TPU v5e at the
# MovieLens-1M shape, 92.9 ns per rating at 8 and 96.7 at 1
UNROLL = 8
# VMEM the two padded factor tables may take: 12 MiB of the 16 MiB a v5e
# kernel may use by default, the rest left to the compiler (a
# described-v5e compile refuses 20 MiB of tables). MovieLens-1M at rank
# 50 takes 5.0 MB.
VMEM_TABLE_BUDGET = 12 * 2**20


def padded_shape(rows: int, rank: int) -> tuple:
    """A factor table as the kernel holds it: rows to a multiple of 8
    sublanes, the rank to a multiple of 128 lanes."""
    return rows + (-rows) % 8, rank + (-rank) % 128


def table_bytes(n_rows: int, n_cols: int, rank: int) -> int:
    """VMEM bytes of both padded float32 factor tables."""
    return 4 * sum(r * c for r, c in (padded_shape(n_rows, rank),
                                      padded_shape(n_cols, rank)))


def _rows_kernel(i_ref, j_ref, v_ref, alpha_ref, left_hbm, right_hbm,
                 left_out, right_out, left, right, sems, *, c_row: float,
                 c_col: float, n_tiles: int):
    t = pl.program_id(0)

    def copy(src, dst, k):
        return pltpu.make_async_copy(src, dst, sems.at[k])

    @pl.when(t == 0)
    def _load():
        ins = (copy(left_hbm, left, 0), copy(right_hbm, right, 1))
        for c in ins:
            c.start()
        for c in ins:
            c.wait()

    def rating(k):
        i, j = i_ref[0, k], j_ref[0, k]
        a = alpha_ref[0, k]
        l = left[pl.ds(i, 1), :]  # [1, R]
        r = right[pl.ds(j, 1), :]
        e = jnp.sum(l * r, axis=1, keepdims=True) - v_ref[0, k]  # [1, 1]
        e2 = e + e
        reg_l = c_row * l
        reg_r = c_col * r
        left[pl.ds(i, 1), :] = l - a * ((reg_l + reg_l) + e2 * r)
        right[pl.ds(j, 1), :] = r - a * ((reg_r + reg_r) + e2 * l)

    def block(b, carry):
        k0 = b * UNROLL
        for k in range(UNROLL):  # unrolled: the scalar reads run ahead
            rating(k0 + k)
        return carry

    jax.lax.fori_loop(0, i_ref.shape[1] // UNROLL, block, 0)

    @pl.when(t == n_tiles - 1)
    def _store():
        # the outputs are the inputs' buffers (aliased)
        outs = (copy(left, left_out, 0), copy(right, right_out, 1))
        for c in outs:
            c.start()
        for c in outs:
            c.wait()


def lmf_fold(i, j, v, alpha, left, right, *, c_row: float, c_col: float,
             interpret: bool = False):
    """Sequential row-sparse IGD over n ratings. i, j: [n] int32, v,
    alpha: [n] float32 (n % TILE == 0); left: [U, R], right: [M, R]
    float32 (U, M multiples of 8, R of 128). Returns (left, right)."""
    n = i.shape[0]
    assert n % TILE == 0, f"n={n} not a multiple of {TILE}"
    for table in (left, right):
        assert table.shape[0] % 8 == 0 and table.shape[1] % 128 == 0, (
            table.shape)
    n_tiles = n // TILE
    col = pl.BlockSpec((None, 1, TILE), lambda t: (t, 0, 0),
                       memory_space=pltpu.SMEM)
    table = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_rows_kernel, c_row=c_row, c_col=c_col,
                          n_tiles=n_tiles),
        grid=(n_tiles,),
        in_specs=[col, col, col, col, table, table],
        out_specs=[table, table],
        out_shape=[jax.ShapeDtypeStruct(left.shape, jnp.float32),
                   jax.ShapeDtypeStruct(right.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM(left.shape, jnp.float32),
                        pltpu.VMEM(right.shape, jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="igd_serial",
    )(
        *(c.reshape(n_tiles, 1, TILE) for c in (i, j, v, alpha)),
        left, right,
    )
