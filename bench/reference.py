"""Pieces that every configuration's plain reference shares.

The references import nothing of the program under test. What they
share with it is the documented meaning of a query's ``seed``: the
model is initialised from ``PRNGKey(seed)``, and the row order of a
shuffled plan comes from the stream ``fold_in(PRNGKey(seed), 0x5EED)``
(a shuffle takes one split of it, and every epoch then takes one more).
That derivation is restated here, and the arithmetic of each technique
is restated in its configuration's module.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the ordering stream's salt: perm_rng = fold_in(PRNGKey(seed), PERM_SALT)
PERM_SALT = 0x5EED
HIGHEST = jax.lax.Precision.HIGHEST


def epoch_orders(ordering: str, seed: int, n: int, epochs: int):
    """The row order of each epoch: None for the stored order, else the
    permutation the query's seed defines."""
    if ordering not in ("clustered", "shuffle_once", "shuffle_always"):
        raise ValueError(f"the reference has no ordering {ordering!r}")
    rng = jax.random.fold_in(jax.random.PRNGKey(seed), PERM_SALT)
    orders, perm = [], None
    for e in range(epochs):
        if ordering == "shuffle_always" or (
            ordering == "shuffle_once" and e == 0
        ):
            rng, sub = jax.random.split(rng)
            perm = jax.random.permutation(sub, n)
        orders.append(perm)
        rng, _ = jax.random.split(rng)
    return orders


def diminishing(alpha0: float, n: int, k0, count: int, dtype):
    """alpha_k = alpha0 / (1 + k / n) for the global steps k0 .. k0+count-1,
    computed in float32 and handed to the fold in ``dtype``."""
    k = (k0 + jnp.arange(count)).astype(jnp.float32)
    return (alpha0 / (1.0 + k / n)).astype(dtype)


def model_gap(model, ref) -> float:
    """The worst leaf's max|model - ref| / max|ref|; infinite where
    either side is not finite (a diverged fit is never a match)."""
    gaps = []
    for a, b in zip(jax.tree.leaves(model), jax.tree.leaves(ref)):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        if not (np.all(np.isfinite(a)) and np.all(np.isfinite(b))):
            return math.inf
        scale = float(np.max(np.abs(b)))
        diff = float(np.max(np.abs(a - b)))
        gaps.append(diff / scale if scale > 0 else diff)
    return max(gaps)


def loss_gap(loss: float, ref: float) -> float:
    """|loss - ref| relative to the reference's loss, or to 1.0 where
    that is smaller: the losses are sums over rows, and a fit that
    separates the table (a hinge loss of 0) must not divide by 0.
    Infinite where either loss is not finite."""
    if not (math.isfinite(loss) and math.isfinite(ref)):
        return math.inf
    return abs(float(loss) - float(ref)) / max(abs(float(ref)), 1.0)
