"""Public jit'd wrappers for the fused IGD kernels.

These are the lane bodies behind the EpochProgram compiler's
``implementation`` axis (``repro.engine.program.build_program`` lowers
serial lane bodies of kernel-eligible plans through ``igd_fold``, or
``lmf_fold`` for ratings; the planner prices them against the XLA fold
from micro-probes — see ``repro.engine.probes``). ``interpret``
defaults to the backend (``default_interpret``): interpret mode on the
CPU, compiled on a TPU, so no caller runs the interpreter on the chip
by leaving the argument out.

Inputs of any (N, D) are padded to the kernel's (TILE, 128) tiling by
``_pad``. Padded rows carry ``alpha = 0``, so their transitions are
bitwise no-ops for every loss (including ``lsq``, where the pad's margin
is w·x with y = 0 — the step is ``alpha * (margin - y) * x`` and the
zero alpha kills it). Padded columns are zero, so they add nothing to
the margin, but they change the length of the margin's reduction and
with it the order of its additions: D padding agrees with the unpadded
fold to fp32 tolerance, not bit for bit (pinned by
tests/test_kernels.py).

``lmf_fold`` is the row-sparse kernel for low-rank factorization
(``rows.py``): it pads the factor tables to the kernel's layout and the
ratings to its tile, and unpads what comes back. Under ``jax.vmap`` (the
fused serving lanes, the sharded blocks' lanes) it runs the kernel once
per lane, in turn: each call holds one lane's tables in VMEM."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.igd_fused import kernel as K
from repro.kernels.igd_fused import ref as R
from repro.kernels.igd_fused import rows as RK


def default_interpret() -> bool:
    """Interpret-mode on CPU, compiled on real TPU hardware."""
    return jax.default_backend() != "tpu"


def _resolve(interpret):
    return default_interpret() if interpret is None else interpret


def _pad(x, y, alpha, w0):
    n, d = x.shape
    dp = (-d) % 128
    np_ = (-n) % K.TILE
    if dp:
        x = jnp.pad(x, ((0, 0), (0, dp)))
        w0 = jnp.pad(w0, (0, dp))
    if np_:
        x = jnp.pad(x, ((0, np_), (0, 0)))
        y = jnp.pad(y, (0, np_))
        alpha = jnp.pad(alpha, (0, np_))  # alpha=0 -> padded rows are no-ops
    return x, y, alpha, w0, d


@functools.partial(jax.jit, static_argnames=("loss", "interpret", "use_kernel"))
def igd_fold(x, y, alpha, w0, *, loss="lr", interpret=None, use_kernel=True):
    """Bismarck transition fold over (x, y) with per-step sizes alpha."""
    if not use_kernel:
        return R.igd_fold_ref(x, y, alpha, w0, loss=loss)
    xp, yp, ap, wp, d = _pad(x, y, alpha, w0)
    out = K.igd_fold(xp, yp, ap, wp, loss=loss,
                     interpret=_resolve(interpret))
    return out[:d]


def _lmf_padded(i, j, v, alpha, left, right, *, c_row, c_col, interpret):
    pad = (-i.shape[0]) % RK.TILE  # alpha = 0 -> padded ratings are no-ops
    cols = [jnp.pad(c, (0, pad)) for c in (i, j, v, alpha)]
    tables = [
        jnp.pad(t, [(0, p - s) for p, s in zip(RK.padded_shape(*t.shape),
                                               t.shape)])
        for t in (left, right)
    ]
    out = RK.lmf_fold(*cols, *tables, c_row=c_row, c_col=c_col,
                      interpret=interpret)
    return tuple(o[:t.shape[0], :t.shape[1]] for o, t in
                 zip(out, (left, right)))


@functools.lru_cache(maxsize=None)
def _lmf_lanes(c_row: float, c_col: float, interpret: bool):
    @custom_vmap
    def fold(i, j, v, alpha, left, right):
        return _lmf_padded(i, j, v, alpha, left, right, c_row=c_row,
                           c_col=c_col, interpret=interpret)

    @fold.def_vmap
    def _lanes(axis_size, in_batched, *args):
        # one kernel call per lane: the tables of one lane fill the VMEM
        # budget the kernel is admitted under, so lanes run in turn
        del axis_size

        def lane(xs):
            xs = iter(xs)
            return fold(*(next(xs) if b else a
                          for a, b in zip(args, in_batched)))

        out = jax.lax.map(lane, [a for a, b in zip(args, in_batched) if b])
        return out, (True, True)

    return fold


@functools.partial(jax.jit, static_argnames=("c_row", "c_col", "interpret"))
def lmf_fold(i, j, v, alpha, left, right, *, c_row: float, c_col: float,
             interpret=None):
    """Sequential row-sparse lmf IGD over ratings (i, j, v) with
    per-rating step sizes alpha, from factor tables left [U, r] and
    right [M, r]; ``c_row`` / ``c_col`` are mu over the mean user / movie
    degree. Returns the updated (left, right)."""
    return _lmf_lanes(c_row, c_col, _resolve(interpret))(
        i, j, v, alpha, left, right)
