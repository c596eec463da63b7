"""Low-rank matrix factorization (paper Fig. 1B, Recommendation):

    min_{L,R}  sum_{(i,j) in Omega} (L_i . R_j - M_ij)^2 + mu ||L,R||_F^2

Per-rating IGD touches only row L_i and row R_j (the Gemulla et al. /
Bismarck LMF transition): ``example_rows`` names those two rows, and the
engine's transition (``core.uda.IGDAggregate``) gathers them, takes
``jax.grad`` of ``example_loss`` on the two rows and writes back only
them, so a step costs O(rank) whatever the size of the factor tables.
``jax.grad`` over the whole model (``example_grad``) gives the same rows
and zeros everywhere else. Regularization is localized to the touched rows,
scaled down by the rows' expected appearance counts (the standard weighted
trick), so the transition stays O(rank): summing the per-example penalty
over one epoch recovers ~``mu * ||L,R||_F^2`` exactly once, matching
``full_loss``. The degrees therefore MUST reflect the table
(``n_ratings / n_rows`` and ``n_ratings / n_cols``); the 1.0 defaults mean
"each row rated once" and over-penalize dense tables by the mean degree —
pass them explicitly or use :meth:`degrees_for`."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.tasks.base import Task


@dataclasses.dataclass(frozen=True)
class LowRankMF(Task):
    n_rows: int
    n_cols: int
    rank: int
    mu: float = 1e-2
    init_scale: float = 0.1
    # expected #ratings per row/col, used to apportion the global
    # Frobenius penalty onto per-example terms (see module docstring)
    mean_row_degree: float = 1.0
    mean_col_degree: float = 1.0

    @staticmethod
    def degrees_for(n_rows: int, n_cols: int, n_ratings: int) -> dict:
        """Degree apportionment for a table of ``n_ratings`` triples —
        splice into ``task_args`` so the local regularizer sums to the
        global Frobenius penalty once per epoch."""
        return {
            "mean_row_degree": max(n_ratings / max(n_rows, 1), 1.0),
            "mean_col_degree": max(n_ratings / max(n_cols, 1), 1.0),
        }

    def init_model(self, rng):
        kl, kr = jax.random.split(rng)
        return {
            "L": self.init_scale * jax.random.normal(kl, (self.n_rows, self.rank), jnp.float32),
            "R": self.init_scale * jax.random.normal(kr, (self.n_cols, self.rank), jnp.float32),
        }

    def example_rows(self, ex):
        local = dict(ex, i=jnp.zeros_like(ex["i"]), j=jnp.zeros_like(ex["j"]))
        return {"L": ex["i"], "R": ex["j"]}, local

    def example_loss(self, m, ex):
        li = m["L"][ex["i"]]
        rj = m["R"][ex["j"]]
        err = jnp.dot(li, rj) - ex["v"]
        reg = self.mu * (
            jnp.sum(li * li) / self.mean_row_degree
            + jnp.sum(rj * rj) / self.mean_col_degree
        )
        return err * err + reg

    def regularizer(self, m):
        return jnp.float32(0.0)  # folded into example_loss (local reg)

    def full_loss(self, m, data):
        li = m["L"][data["i"]]
        rj = m["R"][data["j"]]
        err = jnp.sum(li * rj, axis=-1) - data["v"]
        frob = jnp.sum(m["L"] ** 2) + jnp.sum(m["R"] ** 2)
        return jnp.sum(err * err) + self.mu * frob
