"""The Bismarck UDA abstraction: initialize / transition / merge / terminate.

Paper, Section 3.1. A User-Defined Aggregate is the systems abstraction for
IGD: the state is the model (plus a step counter), the transition applies
one incremental gradient step per tuple, merge combines partial states from
shared-nothing workers (model averaging, Zinkevich et al.), and terminate
finalizes the model.

In JAX the "aggregate fold over the tuple stream" is ``jax.lax.scan`` over
the leading axis of the example batch — a non-commutative aggregation with
exactly the UDA's data-access pattern.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Generic, NamedTuple, Optional, TypeVar

import jax
import jax.numpy as jnp

from repro.core import igd as igd_lib

State = TypeVar("State")
Example = TypeVar("Example")


class UDA(Generic[State, Example]):
    """The four-function Bismarck contract (Fig. 3 of the paper)."""

    def initialize(self, rng: jax.Array) -> State:
        raise NotImplementedError

    def transition(self, state: State, example: Example) -> State:
        raise NotImplementedError

    def merge(self, a: State, b: State) -> State:
        raise NotImplementedError

    def terminate(self, state: State) -> Any:
        raise NotImplementedError


class IGDState(NamedTuple):
    """Aggregation context: the model plus meta data (paper §3.1)."""

    model: Any  # pytree
    step: jax.Array  # int32 — number of gradient steps taken
    weight: jax.Array  # float32 — examples folded (for weighted merge)


@dataclasses.dataclass(frozen=True)
class IGDAggregate(UDA):
    """IGD expressed as a UDA for an arbitrary analytics task.

    ``task`` provides ``init_model(rng)`` and ``example_grad(model, ex)``
    (defaulting to ``jax.grad`` of ``example_loss``); this class provides the
    generic four functions. Per the paper, the only task-specific logic
    lives inside the transition's gradient computation.

    Where the task names the rows an example reads (``example_rows``) and
    the prox is the identity, the transition is row-sparse: it gathers
    those rows, takes the gradient on the one-row slices and writes back
    only the updated rows, so a step costs the rows it touches and not the
    whole model. A prox acts on every coordinate at every step, so a task
    with one keeps the dense step.
    """

    task: Any
    step_size: igd_lib.StepSize
    prox: Callable = igd_lib.identity_prox

    def initialize(self, rng: jax.Array) -> IGDState:
        model = self.task.init_model(rng)
        return IGDState(model, jnp.int32(0), jnp.float32(0.0))

    @property
    def row_sparse(self) -> bool:
        """Whether a transition writes only the rows its example reads."""
        return (
            self.task.example_rows is not None
            and self.prox is igd_lib.identity_prox
        )

    def update_bytes(self) -> int:
        """Bytes of model one transition writes: one row of each leaf
        when the transition is row-sparse, else the whole model."""
        model = jax.eval_shape(self.initialize, jax.random.PRNGKey(0)).model
        return sum(
            (leaf.size // leaf.shape[0] if self.row_sparse else leaf.size)
            * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(model)
        )

    def transition(self, state: IGDState, example: Example) -> IGDState:
        alpha = self.step_size(state.step)
        if self.row_sparse:
            model = _row_step(self.task, state.model, example, alpha)
        else:
            grad = self.task.example_grad(state.model, example)
            model = igd_lib.igd_step(state.model, grad, alpha, self.prox)
        return IGDState(model, state.step + 1, state.weight + 1.0)

    def merge(self, a: IGDState, b: IGDState) -> IGDState:
        """Weighted model averaging — IGD is 'essentially algebraic' (§3.3)."""
        tot = a.weight + b.weight
        wa = jnp.where(tot > 0, a.weight / jnp.maximum(tot, 1e-30), 0.5)
        wb = 1.0 - wa
        model = jax.tree.map(lambda x, y: wa * x + wb * y, a.model, b.model)
        return IGDState(model, jnp.maximum(a.step, b.step), tot)

    def terminate(self, state: IGDState) -> Any:
        return state.model


def _row_step(task, model, example, alpha):
    """The IGD step on the rows one example reads: the same gradient of
    ``example_loss`` as the dense step, taken on one-row slices, and the
    same ``w - alpha * g`` on those rows; every other row is left as it
    is, as the dense step's zero gradient leaves it."""
    rows, local = task.example_rows(example)
    sliced = jax.tree.map(
        lambda leaf, r: jax.lax.dynamic_index_in_dim(leaf, r, 0), model, rows
    )
    grad = task.example_grad(sliced, local)
    new = jax.tree.map(lambda w, g: w - alpha * g, sliced, grad)
    return jax.tree.map(
        lambda leaf, r, w: jax.lax.dynamic_update_index_in_dim(leaf, w, r, 0),
        model, rows, new,
    )


class NullAggregate(UDA):
    """The paper's strawman: sees every tuple, computes nothing (Tables 2/3).

    Used to measure the engine's pure data-movement overhead. The state
    folds a trivial checksum of each tuple so XLA cannot dead-code-eliminate
    the tuple reads (it must still stream every example)."""

    def initialize(self, rng):
        del rng
        return jnp.float32(0.0)

    def transition(self, state, example):
        leaf = jax.tree.leaves(example)[0]
        return state + jnp.sum(leaf).astype(jnp.float32)

    def merge(self, a, b):
        return a + b

    def terminate(self, state):
        return state


# ---------------------------------------------------------------------------
# The fold engine
# ---------------------------------------------------------------------------


def fold(uda: UDA, state, examples, unroll: int = 1):
    """Run ``transition`` over the leading axis of ``examples`` (one epoch's
    aggregate). This is the SQL-aggregate data access pattern: one sequential
    pass, state carried through."""

    def body(s, ex):
        return uda.transition(s, ex), None

    state, _ = jax.lax.scan(body, state, examples, unroll=unroll)
    return state


def gather_fold(uda: UDA, state, data, perm, unroll: int = 1):
    """Fold ``transition`` over ``data[perm]`` WITHOUT materializing the
    permuted copy: the row gather rides inside the scan. Produces exactly
    ``fold(uda, state, data[perm])`` — same rows, same order, same floats
    — and is the shuffle-ordering lane of both the fused serving batches
    (``repro.engine.serve``) and the sharded blocks
    (``repro.dist.data_parallel``); keep them on THIS one implementation
    or their bit-parity guarantees drift apart."""

    def body(s, p):
        ex = jax.tree.map(lambda x: x[p], data)
        return uda.transition(s, ex), None

    state, _ = jax.lax.scan(body, state, perm, unroll=unroll)
    return state


def fold_jit(uda: UDA):
    """A jitted fold with donated state (the aggregate runs in place)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state, examples):
        return fold(uda, state, examples)

    return run


def segmented_fold(uda: UDA, state, examples, num_segments: int):
    """Shared-nothing parallel aggregate (paper §3.3, 'Pure UDA Version').

    Splits the stream into ``num_segments`` contiguous partitions, folds each
    independently from the same incoming state (vmap = the parallel workers),
    then ``merge``s the partial states pairwise. On a real mesh the vmap axis
    is a data-parallel mesh axis (``repro.dist.data_parallel``); semantics
    are identical.

    Each worker folds with its merge weight ZEROED: a partial state must
    carry only its own contribution, or re-segmenting an already-merged
    state (the epoch loop's steady state) compounds the incoming weight
    into every lane — weight grew x(num_segments+1) per epoch and
    overflowed float32 into NaN models after ~40 epochs. The outgoing
    weight is the incoming one plus the examples folded, same as serial.
    """
    n = jax.tree.leaves(examples)[0].shape[0]
    if n % num_segments:
        raise ValueError(f"{n} examples not divisible by {num_segments} segments")
    seg = jax.tree.map(
        lambda x: x.reshape((num_segments, n // num_segments) + x.shape[1:]),
        examples,
    )
    lane_state = state
    if isinstance(state, IGDState):
        lane_state = IGDState(state.model, state.step, jnp.float32(0.0))
    states = jax.vmap(lambda ex: fold(uda, lane_state, ex))(seg)

    merged = jax.tree.map(lambda x: x[0], states)
    for i in range(1, num_segments):
        merged = uda.merge(merged, jax.tree.map(lambda x, i=i: x[i], states))
    if isinstance(state, IGDState):
        merged = IGDState(merged.model, merged.step, state.weight + n)
    return merged


# ---------------------------------------------------------------------------
# Epoch driver (Fig. 2: the loop around the aggregate)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunResult:
    model: Any
    losses: list  # loss after each epoch
    epochs: int
    shuffle_seconds: float
    gradient_seconds: float
    converged: bool


def run_igd(
    uda: UDA,
    data,
    *,
    rng: jax.Array,
    epochs: int,
    ordering=None,
    loss_fn: Optional[Callable] = None,
    stop=None,
    num_segments: int = 1,
    state=None,
):
    """The Bismarck outer loop: [reorder] -> aggregate -> loss -> converged?

    ``ordering`` is a policy from ``repro.core.ordering`` (None = clustered,
    i.e. the stream's stored order). ``loss_fn(model, data) -> scalar`` is
    the piggybacked objective evaluation; ``stop`` a convergence rule from
    ``repro.core.convergence``.
    """
    from repro.core import ordering as ordering_lib  # local import, no cycle

    if ordering is None:
        ordering = ordering_lib.Clustered()
    if state is None:
        state = uda.initialize(rng)

    n = jax.tree.leaves(data)[0].shape[0]
    perm_rng = jax.random.fold_in(rng, 0x5EED)

    if num_segments == 1:
        folder = jax.jit(lambda s, ex: fold(uda, s, ex))
    else:
        folder = jax.jit(
            lambda s, ex: segmented_fold(uda, s, ex, num_segments)
        )
    loss_jit = jax.jit(loss_fn) if loss_fn is not None else None

    losses = []
    shuffle_s = 0.0
    grad_s = 0.0
    converged = False
    epoch = 0
    for epoch in range(1, epochs + 1):
        t0 = time.perf_counter()
        examples, perm_rng = ordering.order(data, n, epoch, perm_rng)
        jax.block_until_ready(examples)
        t1 = time.perf_counter()
        state = folder(state, examples)
        jax.block_until_ready(state)
        t2 = time.perf_counter()
        shuffle_s += t1 - t0
        grad_s += t2 - t1
        if loss_jit is not None:
            losses.append(float(loss_jit(uda.terminate(state), data)))
        if stop is not None and stop(losses, epoch):
            converged = True
            break

    return RunResult(
        model=uda.terminate(state),
        losses=losses,
        epochs=epoch,
        shuffle_seconds=shuffle_s,
        gradient_seconds=grad_s,
        converged=converged,
    )
