"""Pure-jnp oracles for the fused IGD kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _grad_scale(loss, margin, y):
    if loss == "lr":
        return -y * jax.nn.sigmoid(-margin)
    if loss == "svm":
        return jnp.where(margin < 1.0, -y, 0.0)
    if loss == "lsq":
        return margin - y
    raise ValueError(loss)


def igd_fold_ref(x, y, alpha, w0, *, loss: str = "lr"):
    """Sequential per-example IGD via lax.scan (the UDA fold)."""

    def body(w, ex):
        xi, yi, ai = ex
        wx = jnp.dot(w, xi)
        m = wx if loss == "lsq" else yi * wx
        c = _grad_scale(loss, m, yi) * ai
        return w - c * xi, None

    w, _ = jax.lax.scan(body, w0, (x, y, alpha))
    return w


def lmf_fold_ref(i, j, v, alpha, left, right, *, c_row: float, c_col: float):
    """Sequential row-sparse lmf IGD via lax.scan: rating k steps row
    i[k] of ``left`` and row j[k] of ``right`` by ``jax.grad`` of the
    task's per-rating loss, in the same form (``c = mu / degree``)."""

    def body(lr, ex):
        left, right = lr
        ii, jj, vv, a = ex
        l, r = left[ii], right[jj]
        e2 = 2.0 * (jnp.dot(l, r) - vv)
        gl = (c_row * l + c_row * l) + e2 * r
        gr = (c_col * r + c_col * r) + e2 * l
        return (left.at[ii].set(l - a * gl), right.at[jj].set(r - a * gr)), None

    (left, right), _ = jax.lax.scan(body, (left, right), (i, j, v, alpha))
    return left, right
