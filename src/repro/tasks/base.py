"""Task protocol: the ~10-lines-of-code contract from the paper (Fig. 4).

A task defines ``init_model`` and ``example_loss``; ``example_grad`` comes
for free from ``jax.grad`` (tasks may override it with a hand-written
gradient, mirroring the paper's hand-coded transitions). ``full_loss`` is
the piggybacked objective evaluation used by convergence tests. A task
whose example reads a few rows of its model names them in
``example_rows``, so a transition touches those rows only."""

from __future__ import annotations

import jax
import jax.numpy as jnp


class Task:
    def init_model(self, rng: jax.Array):
        raise NotImplementedError

    def example_loss(self, model, example) -> jax.Array:
        raise NotImplementedError

    def example_grad(self, model, example):
        return jax.grad(self.example_loss)(model, example)

    # Optional hook, for a task whose example reads one row of each model
    # leaf: ``example_rows(example) -> (rows, local)``, the row index per
    # leaf (a pytree shaped like the model) and the example re-indexed to
    # read row 0 of a one-row slice of each leaf. The IGD transition then
    # gathers those rows, differentiates ``example_loss`` on the slices
    # and writes back only them. None: an example may read every
    # coordinate, and a transition updates the whole model.
    example_rows = None

    def regularizer(self, model) -> jax.Array:
        return jnp.float32(0.0)

    def full_loss(self, model, data) -> jax.Array:
        per = jax.vmap(lambda ex: self.example_loss(model, ex))(data)
        return jnp.sum(per) + self.regularizer(model)
