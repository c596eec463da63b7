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
  seen again) loads it after that store: the fold is the sequential one;
* a block of ``BLOCK`` ratings of one user and distinct movies takes its
  errors from the block recurrence below instead, the rows still updated
  rating by rating.

The gradient is ``jax.grad`` of ``example_loss`` in the form it takes on
one-row slices (``uda._row_step``), with ``e = l . r - v`` and
``c = mu / degree``::

    l' = l - alpha * ((c_row * l + c_row * l) + 2e * r)
    r' = r - alpha * ((c_col * r + c_col * r) + 2e * l)

The block recurrence. One rating at a time, rating k's error waits on
the lane reduction ``l . r_k`` against the row the rating before it
stored: a latency chain, about 140 cycles per rating on a v5e. A block
of B ratings of one user i, with pairwise distinct movies ``j_0..j_{B-1}``,
shares ``l = L[i]``, and no movie row of the block is written before the
block's own rating reads it. So the B movie rows are loaded once, as
``R_B`` ([B, R], their values at the block's start), and with ``l_k``
the user row before rating k::

    q = R_B l_0,  G = R_B R_B^T            (once per block, off the chain)
    e_k = q_k - v_k,                       q_k = l_k . r_k
    l_{k+1} = l_k - a_k ((c_row l_k + c_row l_k) + 2 e_k r_k)
    =>  q <- q - a_k ((c_row q + c_row q) + 2 e_k G[:, k])

since ``R_B l_{k+1}`` is that combination of ``R_B l_k`` and ``R_B r_k``.
Each rating then costs its error and a [B, 1] update of q; both the Gram
columns and q are float32 lane reductions on the VPU, as ``kernel.py``
takes them for the dense block. The rows are updated in the fold's own
arithmetic form: ``R[j_k]`` from ``r_k`` and the current ``l_k``, then
``l`` in vregs, stored once at the block's end. Only the errors come
from the block, so the factors move from the sequential fold's by
rounding alone.

Which blocks qualify is read from the ids, not set by a flag:
``one_user_blocks`` marks each rating whose block has one user and
pairwise distinct movies, elementwise XLA over the id columns outside
the kernel, and the marks ride into SMEM as a fifth column beside the
ratings; the kernel reads the mark of each block's first rating. Any other
block (a user boundary inside it, a movie seen twice, the ``alpha = 0``
tail padding with its repeated ``i = j = 0``) is folded one rating at a
time, as above. A stream stored sorted by user (MovieLens-1M: 165.6
ratings per user on average) takes the block path almost everywhere; a
shuffled one almost nowhere.

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
# ratings per block of the in-tile loop, one [8, 128] tile of movie rows
BLOCK = 8
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


def _shifted(x, d: int, fill):
    """x[:, t - d] along the tile axis of x [n // TILE, TILE] (for d < 0
    the rating |d| later), ``fill`` where that falls outside the tile."""
    pad = jnp.full((x.shape[0], abs(d)), fill, x.dtype)
    if d > 0:
        return jnp.concatenate([pad, x[:, :-d]], axis=1)
    return jnp.concatenate([x[:, -d:], pad], axis=1)


def one_user_blocks(i, j):
    """[n] int32, each rating's block of ``BLOCK`` consecutive ratings
    marked: 1 where the block's ratings all have one user and pairwise
    distinct movies (the kernel folds it from the block recurrence), 0
    for any other block (folded one rating at a time). Elementwise over
    the id columns in their tiles, ``n % TILE == 0``: a block never
    straddles a tile, and no [*, BLOCK]-shaped relayout is made."""
    n = i.shape[0]
    i, j = (c.reshape(n // TILE, TILE) for c in (i, j))
    pos = jax.lax.broadcasted_iota(jnp.int32, i.shape, 1) % BLOCK
    # a rating breaks its block if its user is not the one before it,
    # or a rating before it in the block names its movie
    breaks = (pos > 0) & (i != _shifted(i, 1, 0))
    for d in range(1, BLOCK):
        breaks |= (pos >= d) & (j == _shifted(j, d, 0))
    broken = breaks  # then any break in the block marks all of it
    for d in range(1, BLOCK):
        broken |= (pos >= d) & _shifted(breaks, d, False)
        broken |= (pos + d < BLOCK) & _shifted(breaks, -d, False)
    return jnp.logical_not(broken).astype(jnp.int32).reshape(n)


@jax.jit
def block_share(i, j):
    """The share of a stream's n ratings that the block recurrence folds:
    the ratings of its ``one_user_blocks``, the stream padded as the
    kernel's callers pad it, with ``alpha = 0`` ratings (``i = j = 0``)
    to a whole tile."""
    n = i.shape[0]
    pad = (-n) % TILE
    marks = one_user_blocks(*(jnp.pad(c, (0, pad)) for c in (i, j)))
    return jnp.sum(marks[:n]) / n


def _rows_kernel(i_ref, j_ref, v_ref, alpha_ref, one_user_ref, left_hbm,
                 right_hbm, left_out, right_out, left, right, sems, *,
                 c_row: float, c_col: float, n_tiles: int):
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

    def user_block(k0):
        # one user's ratings of distinct movies: no row is read after
        # this block writes it, except the user's, which stays in vregs
        i = i_ref[0, k0]
        js = [j_ref[0, k0 + k] for k in range(BLOCK)]
        rs = [right[pl.ds(j, 1), :] for j in js]  # [1, R] each
        rb = jnp.concatenate(rs, axis=0)  # [BLOCK, R]
        l = left[pl.ds(i, 1), :]
        # every rating's dot against the block's starting user row, and
        # the Gram columns G[:, k] = R_B r_k, which no update waits on
        q = jnp.sum(rb * l, axis=1, keepdims=True)  # [BLOCK, 1]
        gram = [jnp.sum(rb * r, axis=1, keepdims=True) for r in rs]
        for k in range(BLOCK):
            a = alpha_ref[0, k0 + k]
            r = rs[k]
            e2 = q[k:k + 1, :] - v_ref[0, k0 + k]  # [1, 1]
            e2 = e2 + e2
            reg_r = c_col * r
            right[pl.ds(js[k], 1), :] = r - a * ((reg_r + reg_r) + e2 * l)
            reg_l = c_row * l
            l = l - a * ((reg_l + reg_l) + e2 * r)
            reg_q = c_row * q  # the later ratings' dots against the new l
            q = q - a * ((reg_q + reg_q) + e2 * gram[k])
        left[pl.ds(i, 1), :] = l

    def block(b, carry):
        k0 = b * BLOCK

        @pl.when(one_user_ref[0, k0] != 0)
        def _block():
            user_block(k0)

        @pl.when(one_user_ref[0, k0] == 0)
        def _one_by_one():
            for k in range(BLOCK):  # unrolled: the scalar reads run ahead
                rating(k0 + k)

        return carry

    jax.lax.fori_loop(0, i_ref.shape[1] // BLOCK, block, 0)

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
        in_specs=[col, col, col, col, col, table, table],
        out_specs=[table, table],
        out_shape=[jax.ShapeDtypeStruct(left.shape, jnp.float32),
                   jax.ShapeDtypeStruct(right.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM(left.shape, jnp.float32),
                        pltpu.VMEM(right.shape, jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="igd_serial",
    )(
        *(c.reshape(n_tiles, 1, TILE)
          for c in (i, j, v, alpha, one_user_blocks(i, j))),
        left, right,
    )
