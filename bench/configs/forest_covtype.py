"""Forest covertype: a dense table of +-1 labelled rows, stored clustered
by label, and the dense GLM techniques (logreg, svm) over it.

The generator is a copy of ``repro.data.synthetic.dense_classification``
made into one jitted call, so that a change to the program cannot move
the data. The reference is sequential incremental gradient descent in
plain ``jax.numpy``: one ``lax.scan`` step per row, with the catalog's
step sizes and no proximal term (mu = 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import reference as ref_lib

F32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("n", "dim", "margin", "noise"))
def _dense_classification(key, *, n, dim, margin, noise):
    kw, kx, kn = jax.random.split(key, 3)
    w_true = jax.random.normal(kw, (dim,)) / jnp.sqrt(dim)
    half = n // 2
    y = jnp.concatenate([jnp.ones(half), -jnp.ones(n - half)]).astype(F32)
    x = jax.random.normal(kx, (n, dim)) / jnp.sqrt(dim)
    proj = x @ w_true
    x = x + ((margin * y - proj) / jnp.sum(w_true**2))[:, None] * w_true[None, :]
    x = x + noise * jax.random.normal(kn, (n, dim)) / jnp.sqrt(dim)
    return {"x": x.astype(F32), "y": y}


def generate(cfg, key):
    """The table, clustered by label (+1 rows first), made on the device."""
    g = cfg["generator"]
    return _dense_classification(
        key, n=cfg["rows"], dim=cfg["features"],
        margin=float(g["margin"]), noise=float(g["noise"]),
    )


def rows(cfg) -> int:
    return cfg["rows"]


def task_args(cfg, task: str) -> dict:
    return {"dim": cfg["features"], "mu": cfg["techniques"][task]["mu"]}


@functools.partial(jax.jit, static_argnames=("task",))
def _epoch(w, x, y, alphas, *, task):
    def body(w, ex):
        xi, yi, ai = ex
        m = yi * jnp.dot(w, xi, precision=ref_lib.HIGHEST)
        if task == "logreg":
            w = w + ai * yi * jax.nn.sigmoid(-m) * xi
        elif task == "svm":
            w = w + jnp.where(1 - m > 0, ai * yi, jnp.zeros_like(ai)) * xi
        else:
            raise ValueError(f"no reference for {task!r}")
        return w, None

    return jax.lax.scan(body, w, (x, y, alphas))[0]


def reference_fit(cfg, data, task, seed, epochs, ordering, dtype=F32):
    """The model after ``epochs`` epochs of ``task`` from the zero model,
    computed in ``dtype`` (float32 for the check, lower for the control)."""
    tech = cfg["techniques"][task]
    if tech["mu"]:
        raise ValueError("the reference has no proximal step (mu must be 0)")
    x = data["x"].astype(dtype)
    y = data["y"].astype(dtype)
    n, d = x.shape
    w = jnp.zeros((d,), dtype)
    for e, perm in enumerate(ref_lib.epoch_orders(ordering, seed, n, epochs)):
        xe, ye = (x, y) if perm is None else (x[perm], y[perm])
        alphas = ref_lib.diminishing(tech["alpha0"], n, e * n, n, dtype)
        w = _epoch(w, xe, ye, alphas, task=task)
    return w.astype(F32)


@functools.partial(jax.jit, static_argnames=("task",))
def _loss(w, x, y, *, task):
    m = y * jnp.dot(x, w, precision=ref_lib.HIGHEST)
    if task == "logreg":
        return jnp.sum(jnp.logaddexp(0.0, -m))
    return jnp.sum(jnp.maximum(1.0 - m, 0.0))


def reference_loss(cfg, data, task, model, dtype=F32) -> float:
    """The summed objective over the whole table (mu = 0: no penalty),
    computed in ``dtype``."""
    return float(_loss(model.astype(dtype), data["x"].astype(dtype),
                       data["y"].astype(dtype), task=task))


def epoch_work(cfg, task, lanes: int):
    """(operations, HBM bytes) of one epoch of ``lanes`` fits that share
    the table, from the published shape: each lane does a dot and an
    axpy per row (4 d operations), and the table (d features and a
    label, 4 bytes each) is read once for all lanes."""
    n, d = cfg["rows"], cfg["features"]
    return 4 * n * d * lanes, n * (d + 1) * 4
