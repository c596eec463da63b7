"""repro.engine.program — the EpochProgram IR and its one compiler.

The paper's thesis is that a *unified* architecture lets ordering and
parallelism optimizations be studied generically instead of
per-technique. The executor layer had re-grown four ad-hoc epoch
builders (the singleton executor's epoch functions, the serving
front-end's fused batches, the sharded local-SGD blocks, and the
standalone drivers in ``repro.core``), so every new axis had to be
bolted onto each path separately. This module is the fix: ONE
intermediate representation with four orthogonal axes and ONE compiler
that lowers any combination of them to a jitted block.

The axes
========

* **ordering** — ``sequential``/``clustered`` (the stored order; the
  two names are aliases — "clustered" when the storage layer clustered
  the heap, "sequential" otherwise), ``shuffle_once``, or
  ``shuffle_always`` (paper §3.2). Carried by ``Plan.ordering``.
* **parallelism** — ``singleton`` (one device runs the plan's scheme:
  serial fold, segmented fold, or buffered MRS) or ``sharded(k, H)``
  (k shared-nothing segments over a device mesh, merge-period-H local
  SGD — §3.3 at mesh scale). Carried by ``Plan.parallelism``/
  ``num_shards``/``merge_period``/``shard_devices``.
* **query batching** — ``B`` fused query lanes, each with its own
  threefry rng stream and its own *epoch budget*: every fused run takes
  a ``budgets[B]`` vector and freezes a lane's state once its budget is
  spent (``jnp.where`` per epoch), so queries that differ only in
  ``epochs`` fuse into one executable. A homogeneous batch is the
  special case where every mask is True — bit-identical to the
  pre-mask fused path.
* **data source** — ``memory`` (one resident pytree) or ``table`` (a
  stored-table chunk stream via the duck-typed ``Table`` protocol —
  see ``repro.engine.table``). Carried by ``Plan.source``.
* **implementation** — ``xla_fold`` (the generic ``uda.fold`` scan) or
  ``pallas_fused`` (the fused-IGD Pallas kernel,
  ``repro.kernels.igd_fused``: model hot in VMEM while example tiles
  stream past — the paper's Bismarck inner loop as a real kernel; for
  ``lmf`` the row kernel, both factor tables in VMEM while ratings
  stream past). Serial lane bodies only; eligibility is a catalog
  property (``TaskSpec.kernel_loss`` + identity prox, and for ``lmf`` a
  row-sparse transition whose tables fit the kernel's VMEM — see
  :func:`kernel_eligibility`). The planner prices it from micro-probes
  (``probes.Calibration.impl_per_row``). Carried by
  ``Plan.implementation``.

RNG discipline
==============

Every composition derives its streams exactly like the singleton
executor: ``init_rng = PRNGKey(seed)``, ``perm_rng = fold_in(init_rng,
PERM_STREAM_SALT)``, one ordering split per shuffle, one executor split
per epoch. Batched lanes use vmapped threefry ops, which are
elementwise over keys and therefore bit-identical to the per-key serial
calls. That is what makes every composition at ``k=1``/``B=1``
reproduce the singleton executor's floats exactly (pinned by
``tests/test_program.py``).

Compile counting
================

All executables go through ``repro.core.tracecount.counted_jit``: each
compiled program carries a per-program retrace counter (the cache
tests' observable) and every retrace also lands in the process-wide
tally (``tracecount.GLOBAL``), including the standalone drivers in
``repro.core`` (MRS and the Fig 9 concurrency simulator).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import mrs as mrs_lib, ordering as ordering_lib
from repro.core import uda as uda_lib
from repro.core.tracecount import counted_jit, fresh_counter
from repro.dist import data_parallel as dp
from repro.launch import mesh as mesh_lib

# Salt deriving the ordering/permutation rng stream from a query's seed:
#   perm_rng = fold_in(PRNGKey(seed), PERM_STREAM_SALT)
# Every execution path (singleton, fused, sharded) derives its streams
# from this one discipline — change it here and only here.
PERM_STREAM_SALT = 0x5EED

# "sequential" is the stored order by another name (the storage layer
# just didn't cluster it); the IR canonicalizes so downstream code has
# exactly three physical orderings.
ORDERING_ALIASES = {"sequential": "clustered"}

# ordering -> sharded block mode (the epoch-stream layouts)
SHARD_MODES = {
    "clustered": "segments",
    "shuffle_once": "perm_once",
    "shuffle_always": "perm_epoch",
}

# The implementation axis: how a serial lane body is lowered.
#   xla_fold        — the generic unified-aggregate scan (uda.fold)
#   pallas_fused    — kernels/igd_fused per-tuple IGD (ref.py oracle:
#                     bit-order-identical to the scan, fp32 tolerance);
#                     lmf: the per-rating row kernel (rows.py)
IMPLEMENTATIONS = ("xla_fold", "pallas_fused")
# the catalog kernel_loss that lowers through the row kernel
# (kernels/igd_fused/rows.py) instead of the dense one
ROW_KERNEL_LOSS = "lmf"


def canonical_ordering(name: str) -> str:
    return ORDERING_ALIASES.get(name, name)


def kernel_eligibility(task, agg) -> Tuple[Optional[str], str]:
    """(kernel loss name, "") when the aggregate can lower through a
    fused-IGD kernel, else (None, reason). Eligibility is a catalog
    property: the task's exact class must be registered with a
    ``kernel_loss`` AND the aggregate must carry the identity prox — the
    kernels' transitions have no prox hook, so an L1 ball or simplex
    projection would silently be skipped.

    ``lr``/``svm``/``lsq`` take the dense kernel (``igd_fused.kernel``)
    over ``{x, y}`` rows. ``lmf`` takes the row kernel
    (``igd_fused.rows``) over ``{i, j, v}`` ratings: the aggregate's
    transition must be row-sparse, and both factor tables, padded to
    the kernel's layout, must fit its VMEM budget
    (``rows.VMEM_TABLE_BUDGET``), since it holds them in VMEM for the
    whole epoch."""
    from repro.core import igd as igd_lib
    from repro.engine import catalog

    loss = catalog.kernel_loss_for(task)
    if loss is None:
        return None, (
            f"task {type(task).__name__} has no kernel_loss in the catalog "
            "(only dense lr/svm/lsq and row-sparse lmf transitions match "
            "a kernel)"
        )
    if agg.prox is not igd_lib.identity_prox:
        return None, (
            "the fused kernel's transition has no prox hook; this "
            "aggregate carries a non-identity prox"
        )
    if loss == ROW_KERNEL_LOSS:
        from repro.kernels.igd_fused import rows

        if not agg.row_sparse:
            return None, (
                "the row kernel steps the two rows a rating reads; this "
                "aggregate's transition is dense"
            )
        need = rows.table_bytes(task.n_rows, task.n_cols, task.rank)
        if need > rows.VMEM_TABLE_BUDGET:
            return None, (
                f"the padded factor tables take {need:,} B of VMEM, over "
                f"the row kernel's budget of {rows.VMEM_TABLE_BUDGET:,} B"
            )
    return loss, ""


def require_kernel_loss(task, agg, implementation: str) -> str:
    loss, why = kernel_eligibility(task, agg)
    if loss is None:
        raise ValueError(
            f"implementation={implementation!r} needs a kernel-eligible "
            f"aggregate: {why}"
        )
    return loss


# ---------------------------------------------------------------------------
# the IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EpochProgram:
    """One composed execution: a physical ``Plan`` (ordering ×
    parallelism × scheme × source) plus the serving-time batching axis.
    Hashable — compiled programs are cached on it."""

    plan: Any  # planner.Plan (duck-typed: this module never imports it)
    batch: int = 1  # B fused query lanes (1 = driver-paced singleton)
    shared_table: bool = True  # lanes read one table vs a stacked bank
    # static epoch bound compiled into fused runs (the scan length);
    # per-lane budgets <= epochs mask the tail. 0 = driver-paced.
    epochs: int = 0

    def describe(self) -> str:
        b = f"B={self.batch}"
        if self.batch > 1:
            b += " (per-lane budgets)" if self.epochs else ""
        return self.plan.axes(batch=b)


@dataclasses.dataclass
class CompiledProgram:
    """``build_program``'s output: the jitted block(s) for one axis
    combination. Which callables are populated depends on the axes —
    drivers ask for the combination they drive:

    * ``batch == 1``, singleton parallelism — ``epoch_fn(state,
      examples, rng)`` (MRS: ``(carry, examples, rng)``), one jitted
      counted epoch;
    * ``batch == 1``, sharded — ``runner`` (a :class:`ShardedRunner`
      handing out per-block-length compiled ``shard_map`` blocks);
    * ``batch > 1``, singleton — ``run_fn(states, data, keys, budgets)``
      executes the ENTIRE masked multi-epoch batch as one compiled call
      (plus ``prep_fn``/``init_fn``/``loss_fn``, see ``_build_fused``);
    * ``batch > 1``, sharded — ``init_fn``/``loss_fn`` here; the blocks
      come from the singleton compile's ``runner.batched_block`` so
      fused and singleton sharded queries share executables.
    """

    program: EpochProgram
    task: Any
    agg: Any
    trace_counter: Dict[str, int]
    epoch_fn: Optional[Callable] = None
    runner: Optional["ShardedRunner"] = None
    # fused-batch fields
    mode: Optional[str] = None  # "fused" | "fixed" | "sharded"
    run_fn: Optional[Callable] = None
    prep_fn: Optional[Callable] = None
    init_fn: Optional[Callable] = None
    loss_fn: Optional[Callable] = None

    @property
    def plan(self):
        return self.program.plan

    @property
    def trace_count(self) -> int:
        return self.trace_counter["traces"]


# ---------------------------------------------------------------------------
# rng stream helpers (shared by every composition)
# ---------------------------------------------------------------------------


def seed_streams(seed: int) -> Tuple[jax.Array, jax.Array]:
    """(init_rng, perm_rng) — the singleton executor's derivation."""
    rng = jax.random.PRNGKey(seed)
    return rng, jax.random.fold_in(rng, PERM_STREAM_SALT)


def vsplit(keys):
    """Batched ``rng, sub = jax.random.split(rng)`` — bit-identical to
    the per-query split (threefry is elementwise over keys)."""
    out = jax.vmap(jax.random.split)(keys)
    return out[:, 0], out[:, 1]


# batched (PRNGKey(seed), fold_in(PRNGKey(seed), PERM_STREAM_SALT)) —
# one dispatch for a whole batch's init rngs + ordering streams,
# bit-identical to the per-query derivation above
vseed = jax.jit(jax.vmap(lambda s: (
    jax.random.PRNGKey(s),
    jax.random.fold_in(jax.random.PRNGKey(s), PERM_STREAM_SALT),
)))

# the same gather the ordering policies use
_take = ordering_lib._permute


def _lane_select(keep, new, old, axis: int):
    """Per-lane mask select: ``keep[B]`` gates the query-lane ``axis``
    of every state leaf (frozen lanes keep their old state — the
    masked-epoch mechanism of the batching axis)."""

    def sel(a, b):
        shape = [1] * a.ndim
        shape[axis] = keep.shape[0]
        return jnp.where(keep.reshape(shape), a, b)

    return jax.tree.map(sel, new, old)


# ---------------------------------------------------------------------------
# singleton epoch bodies (B=1, driver-paced)
# ---------------------------------------------------------------------------


def build_epoch_fn(task, agg, plan) -> Callable:
    """The chosen scheme's raw (unjitted) epoch function
    ``(state_or_carry, examples, rng) -> state_or_carry`` — the
    singleton lane body every other composition is built from."""
    impl = plan.implementation
    if impl not in IMPLEMENTATIONS:
        raise ValueError(
            f"unknown implementation {impl!r}; valid: {IMPLEMENTATIONS}"
        )
    if impl != "xla_fold" and plan.scheme != "serial":
        raise ValueError(
            f"implementation={impl!r} lowers the serial lane body; "
            f"scheme={plan.scheme!r} has no kernel form (use "
            "scheme='serial' or implementation='xla_fold')"
        )
    if plan.scheme == "serial":
        if impl != "xla_fold":
            return _kernel_lane_for(task, agg, impl, with_rng=True)
        return lambda s, ex, rng: uda_lib.fold(agg, s, ex, unroll=plan.unroll)
    if plan.scheme == "segmented":
        return lambda s, ex, rng: uda_lib.segmented_fold(
            agg, s, ex, plan.num_segments
        )
    if plan.scheme == "mrs":
        if plan.mrs_buffer <= 0:
            raise ValueError(
                "an MRS plan needs mrs_buffer > 0 (the planner sizes "
                "it from the memory budget)"
            )
        cfg = mrs_lib.MRSConfig(buffer_size=plan.mrs_buffer,
                                ratio=plan.mrs_ratio)

        def mrs_epoch(carry, ex, rng):
            state, buf_a, buf_b, active = carry
            state, buf_a = mrs_lib.mrs_epoch(
                agg, state, ex, buf_a, buf_b, active, cfg, rng
            )
            return (state, buf_a, buf_b, active)

        return mrs_epoch
    raise ValueError(f"unknown scheme {plan.scheme!r}")


def build_chunk_epoch_fn(task, agg, plan, counter) -> Callable:
    """The ``source='table'`` epoch: stream the stored chunk order
    through one counted, donated per-chunk fold with carried state.
    Chunk boundaries are invisible to the result — the transition
    sequence equals folding the concatenated table — and the working
    set is one chunk, which is the whole point of the axis."""
    if plan.scheme != "serial" or plan.ordering != "clustered":
        raise ValueError(
            "source='table' streams the stored order through the serial "
            f"fold; got scheme={plan.scheme!r}, ordering={plan.ordering!r} "
            "(the planner materializes for every other combination)"
        )
    impl = plan.implementation
    if impl != "xla_fold":
        # the kernel folds each chunk with carried state: alphas continue
        # from state.step, so chunk boundaries stay invisible exactly as
        # they are for the scan
        fold_chunk = counted_jit(
            _kernel_lane_for(task, agg, impl), counter, donate_argnums=(0,),
        )
    else:
        fold_chunk = counted_jit(
            lambda s, ex: uda_lib.fold(agg, s, ex, unroll=plan.unroll),
            counter, donate_argnums=(0,),
        )

    def epoch(state, table, rng):
        del rng  # the stored order consumes no randomness
        for chunk in table.chunks():
            state = fold_chunk(state, chunk)
        return state

    return epoch


def permuted_lane(agg, unroll: int):
    """One lane's serial fold following a permutation through the table
    instead of folding a materialized shuffled copy
    (``uda.gather_fold``): the row gather rides inside the scan, so a
    fused batch never writes B permuted copies of the table."""

    def lane(state, data, perm):
        return uda_lib.gather_fold(agg, state, data, perm, unroll=unroll)

    return lane


# ---------------------------------------------------------------------------
# kernel lane bodies (the implementation axis's pallas_fused lowering)
# ---------------------------------------------------------------------------


def kernel_lane_fold(agg, loss: str, *, interpret: Optional[bool] = None):
    """The serial lane body lowered through a fused-IGD Pallas kernel:
    ``(state, ex) -> state`` over one epoch stream, advancing step/weight
    exactly like ``uda.fold`` (one per example). The per-example step
    sizes are the sequential schedule's exact values — transition i
    reads ``step_size(step0 + i)`` and ``StepSize`` is elementwise over
    the step vector, so the kernel sees the same alphas the scan would
    have computed one at a time. A dense ``{"x": [n, d], "y": [n]}``
    stream takes the dense kernel; ``loss="lmf"`` a ``{"i", "j", "v"}``
    ratings stream and the row kernel (:func:`_row_lane`).
    ``interpret=None`` picks per backend (interpret on CPU, compiled on
    TPU — ``igd_fused.ops.default_interpret``)."""
    from repro.kernels.igd_fused import ops as igd_ops

    if loss == ROW_KERNEL_LOSS:
        return _row_lane(agg, interpret)

    def lane(state, ex):
        x, y = ex["x"], ex["y"]
        n = x.shape[0]
        alphas = agg.step_size(state.step + jnp.arange(n))
        model = igd_ops.igd_fold(x, y, alphas, state.model, loss=loss,
                                 interpret=interpret)
        return uda_lib.IGDState(model, state.step + n, state.weight + n)

    return lane


def _row_lane(agg, interpret: Optional[bool]):
    """The lmf lane over the row kernel: the regularizer's scale per row,
    ``mu / degree``, is computed in float32 as ``jax.grad`` of
    ``LowRankMF.example_loss`` computes it."""
    from repro.kernels.igd_fused import ops as igd_ops

    task = agg.task
    mu = np.float32(task.mu)
    c_row = float(mu / np.float32(task.mean_row_degree))
    c_col = float(mu / np.float32(task.mean_col_degree))

    def lane(state, ex):
        n = ex["i"].shape[0]
        alphas = agg.step_size(state.step + jnp.arange(n))
        left, right = igd_ops.lmf_fold(
            ex["i"], ex["j"], ex["v"], alphas,
            state.model["L"], state.model["R"],
            c_row=c_row, c_col=c_col, interpret=interpret,
        )
        return uda_lib.IGDState({"L": left, "R": right}, state.step + n,
                                state.weight + n)

    return lane


def lmf_block_share(task, agg, plan, data) -> Optional[float]:
    """The share of an epoch's ratings that the row kernel folds from its
    one-user blocks (``rows.block_share``) under ``plan``, where its
    stream is the table's stored order: a clustered serial singleton
    ``pallas_fused`` plan of ``lmf`` over an in-memory table. None for
    any other plan, whose stream is not known before its fit. One small
    device reduction and one read, so take it when the plan is compiled,
    never inside a fit."""
    from repro.kernels.igd_fused import rows as rows_kernel

    if (plan.implementation != "pallas_fused"
            or kernel_eligibility(task, agg)[0] != ROW_KERNEL_LOSS
            or (plan.ordering, plan.scheme, plan.parallelism, plan.source)
            != ("clustered", "serial", "singleton", "memory")
            or not isinstance(data, dict)):
        return None
    return float(rows_kernel.block_share(data["i"], data["j"]))


def kernel_permuted_lane(agg, loss: str, *, interpret: Optional[bool] = None):
    """The kernel lane behind a permutation: the kernel streams example
    tiles in array order, so the permutation is applied as one gather up
    front (same rows, same order, same floats as ``permuted_lane``'s
    in-scan gather — the kernel trades the per-step gather for a
    materialized permuted view, which is the layout it wants anyway)."""
    lane = kernel_lane_fold(agg, loss, interpret=interpret)

    def permuted(state, data, perm):
        return lane(state, _take(data, perm))

    return permuted


def _kernel_lane_for(task, agg, implementation: str,
                     with_rng: bool = False):
    """Build the lane body for the pallas_fused implementation
    (validated)."""
    loss = require_kernel_loss(task, agg, implementation)
    lane = kernel_lane_fold(agg, loss)
    if with_rng:
        return lambda s, ex, rng: lane(s, ex)
    return lane


# ---------------------------------------------------------------------------
# sharded compositions: step compensation + the local-SGD blocks
# ---------------------------------------------------------------------------


def compensated_step_size(step_size: Callable, num_shards: int) -> Callable:
    """The linear-scaling schedule for k-way model averaging: shard step
    counters advance once per *local* example and averaging k lane
    displacements shrinks the effective step by ~k, so shards run
    ``alpha'(t) = k * alpha(k * t)``. Identity at k=1 — the singleton
    path is untouched."""
    if num_shards == 1:
        return step_size

    def compensated(t):
        return num_shards * step_size(num_shards * jnp.asarray(t))

    return compensated


def compensated_aggregate(agg, num_shards: int):
    """The aggregate the shards fold with: same transition/merge, the
    compensated schedule."""
    if num_shards == 1:
        return agg
    return dataclasses.replace(
        agg, step_size=compensated_step_size(agg.step_size, num_shards)
    )


def _lane_fold(agg, unroll: int):
    """One shard lane's epoch over its materialized segment."""

    def fold(state, seg):
        return uda_lib.fold(agg, state, seg, unroll=unroll)

    return fold


def build_shard_block(
    agg,
    mesh,
    *,
    num_shards: int,
    block_len: int,
    mode: str,
    n_rows: int,
    unroll: int = 8,
    batch: int = 0,
    implementation: str = "xla_fold",
    kernel_loss: Optional[str] = None,
) -> Callable:
    """One compiled merge-period block: ``block_len`` local epochs then
    one global merge, under ``shard_map`` over the ("shard",) mesh.
    Returns the raw (unjitted) function; callers jit it (counted).

    ``mode`` selects the epoch stream (mirroring the ordering axis):

    * ``"segments"``   — ``block(state, seg)``: contiguous per-lane
      segments, ``seg`` laid out ``P("shard")`` (clustered ordering);
    * ``"perm_once"``  — ``block(state, data, perms)``: the table rides
      replicated, per-lane permutation slices ride sharded and are
      re-used every epoch (shuffle-once);
    * ``"perm_epoch"`` — ``block(state, data, key) -> (state, key)``: a
      fresh epoch permutation is derived in-run from the carried key
      with exactly the singleton executor's split sequence
      (shuffle-always).

    ``state`` is ONE replicated aggregate state in and out: lanes start
    from it with their weight zeroed (partial states must carry only
    their own contribution — see ``uda.segmented_fold``), and the block
    ends with the lane/device merge tree plus a weight restore.

    ``batch = B > 0`` is the fused-serving variant: state (and the
    perm/key streams) carry a leading query axis of B lanes, and the
    block takes two extra trailing arguments ``(budgets[B], done)`` —
    per-lane epoch budgets plus the epochs already completed before
    this block. Each in-block epoch freezes lanes whose budget is
    spent, so heterogeneous-epoch batches compose with every ordering;
    a frozen lane's partials stop moving, which makes the block-end
    merge equal the merge the lane's own (shorter) singleton run would
    have performed. A homogeneous batch masks nothing and is
    bit-identical to the pre-mask fused path.

    ``implementation``/``kernel_loss`` select the lane body's lowering
    (the implementation axis): ``pallas_fused`` swaps the per-lane fold for
    the fused-IGD kernel — same alphas, same step/weight accounting, so
    the block's merge tree and compensated schedule are untouched.
    """
    AXIS = dp.AXIS
    num_devices = mesh.devices.size
    if num_shards % num_devices:
        raise ValueError(
            f"{num_shards} shards not divisible by {num_devices} devices"
        )
    lanes = num_shards // num_devices
    rows_per_shard = n_rows // num_shards
    batched = batch > 0
    if mode not in ("segments", "perm_once", "perm_epoch"):
        raise ValueError(f"unknown block mode {mode!r}")
    if implementation != "xla_fold":
        if kernel_loss is None:
            raise ValueError(
                f"implementation={implementation!r} shard blocks need the "
                "kernel_loss resolved by the caller (require_kernel_loss)"
            )
        if mode == "segments":
            lane = kernel_lane_fold(agg, kernel_loss)
        else:
            lane = kernel_permuted_lane(agg, kernel_loss)
    elif mode == "segments":
        lane = _lane_fold(agg, unroll)
    else:
        # the ONE gather-fold lane (shared with the fused serving
        # batches): same rows, same order, same floats as folding a
        # materialized permuted copy, without writing one per lane
        lane = permuted_lane(agg, unroll)

    def lane_start(state):
        # partial states carry only their own contribution to the merge
        # (zeros_like keeps the batched path's [B]-shaped weights)
        if isinstance(state, uda_lib.IGDState):
            return uda_lib.IGDState(
                state.model, state.step, jnp.zeros_like(state.weight)
            )
        return state

    def lane_end(merged, state_in):
        if isinstance(merged, uda_lib.IGDState):
            folded = jnp.float32(block_len * n_rows)
            return uda_lib.IGDState(
                merged.model, merged.step, state_in.weight + folded
            )
        return merged

    def broadcast_lanes(start):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (lanes,) + x.shape), start
        )

    def merge_tree(states):
        merged = dp.merge_stacked(agg, states, lanes, batched=batched)
        return dp.device_merge(agg, merged, num_devices, batched=batched)

    # -- the un-batched (B=1 singleton-driver) blocks -------------------
    # kept byte-for-byte equivalent to the pre-IR construction: the k=1
    # bit-parity and placement-independence pins ride on them

    def epochs_then_merge(state_in, run_epoch):
        states = broadcast_lanes(lane_start(state_in))

        def body(sts, _):
            return run_epoch(sts), None

        states, _ = jax.lax.scan(body, states, None, length=block_len)
        return lane_end(merge_tree(states), state_in)

    # -- the batched (fused-serving) blocks: masked epochs --------------

    def masked_epochs_then_merge(state_in, run_epoch, budgets, done):
        states = broadcast_lanes(lane_start(state_in))

        def body(sts, t):
            new = run_epoch(sts)
            keep = (done + t) < budgets  # [B]
            return _lane_select(keep, new, sts, axis=1), None

        states, _ = jax.lax.scan(body, states, jnp.arange(block_len))
        return lane_end(merge_tree(states), state_in)

    vmap_lane = jax.vmap  # over the per-device lane axis

    def vlane_batched(fn):
        """lanes × query-lanes nest: fn(one_state, one_lane_input)."""
        return vmap_lane(lambda sB, xB: jax.vmap(fn)(sB, xB))

    if mode == "segments":
        if batched:

            def inner(state, seg, budgets, done):
                run = lambda sts: vmap_lane(  # noqa: E731
                    lambda sB, ex: jax.vmap(lambda sq: lane(sq, ex))(sB)
                )(sts, seg)
                return masked_epochs_then_merge(state, run, budgets, done)

            in_specs = (P(), P(AXIS), P(), P())
        else:

            def inner(state, seg):
                run = lambda sts: vmap_lane(lane)(sts, seg)  # noqa: E731
                return epochs_then_merge(state, run)

            in_specs = (P(), P(AXIS))
        out_specs = P()

    elif mode == "perm_once":
        if batched:

            def inner(state, data, perms, budgets, done):
                # perms local: [lanes, B, rows_per_shard]
                run = lambda sts: vlane_batched(  # noqa: E731
                    lambda sq, pq: lane(sq, data, pq)
                )(sts, perms)
                return masked_epochs_then_merge(state, run, budgets, done)

            in_specs = (P(), P(), P(AXIS), P(), P())
        else:

            def inner(state, data, perms):
                run = lambda sts: vmap_lane(  # noqa: E731
                    lambda s, p: lane(s, data, p)
                )(sts, perms)
                return epochs_then_merge(state, run)

            in_specs = (P(), P(), P(AXIS))
        out_specs = P()

    else:  # perm_epoch
        if batched:

            def inner(state, data, keys, budgets, done):
                shard_i = jax.lax.axis_index(AXIS)

                def run_epoch(sts, keys):
                    # per-lane singleton streams: ShuffleAlways splits,
                    # then the executor splits again — vmapped threefry
                    # equals each lane's serial derivation
                    keys, psubs = vsplit(keys)
                    perms = jax.vmap(
                        lambda k: jax.random.permutation(k, n_rows)
                    )(psubs)  # [B, n]
                    keys, _ = vsplit(keys)
                    local = jax.lax.dynamic_slice_in_dim(
                        perms, shard_i * lanes * rows_per_shard,
                        lanes * rows_per_shard, axis=1,
                    ).reshape(batch, lanes, rows_per_shard)
                    local = jnp.swapaxes(local, 0, 1)  # [lanes, B, rps]
                    sts = vlane_batched(
                        lambda sq, pq: lane(sq, data, pq)
                    )(sts, local)
                    return sts, keys

                states = broadcast_lanes(lane_start(state))

                def body(carry, t):
                    sts, ky = carry
                    new, ky = run_epoch(sts, ky)
                    keep = (done + t) < budgets
                    return (_lane_select(keep, new, sts, axis=1), ky), None

                (states, keys), _ = jax.lax.scan(
                    body, (states, keys), jnp.arange(block_len)
                )
                return lane_end(merge_tree(states), state), keys

            in_specs = (P(), P(), P(), P(), P())
        else:

            def inner(state, data, key):
                shard_i = jax.lax.axis_index(AXIS)

                def run_epoch(sts, key):
                    # the singleton stream: ShuffleAlways splits then the
                    # executor splits again (executor._execute)
                    key, sub = jax.random.split(key)
                    perm = jax.random.permutation(sub, n_rows)
                    key, _ = jax.random.split(key)
                    local = jax.lax.dynamic_slice_in_dim(
                        perm, shard_i * lanes * rows_per_shard,
                        lanes * rows_per_shard,
                    ).reshape(lanes, rows_per_shard)
                    sts = vmap_lane(
                        lambda s, p: lane(s, data, p)
                    )(sts, local)
                    return sts, key

                states = broadcast_lanes(lane_start(state))

                def body(carry, _):
                    sts, ky = carry
                    sts, ky = run_epoch(sts, ky)
                    return (sts, ky), None

                (states, key), _ = jax.lax.scan(
                    body, (states, key), None, length=block_len
                )
                return lane_end(merge_tree(states), state), key

            in_specs = (P(), P(), P())
        out_specs = (P(), P())

    return jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


class ShardedRunner:
    """Compiled sharded-block executables for one (query key, plan).

    Lives in the executor's compiled-plan cache as the plan's runner:
    repeat queries reuse the jitted blocks (the trace counter stays
    flat — same observable as the singleton executor). Blocks are keyed
    by (mode, length, batch) because the final block of a run may be
    shorter (``epochs % H``) and fused batches share the cache."""

    def __init__(self, task, agg, plan, trace_counter: Dict[str, int]):
        self.task = task
        self.agg = agg  # the registered aggregate (merges, init, terminate)
        self.agg_sharded = compensated_aggregate(agg, plan.num_shards)
        self.plan = plan
        self.trace_counter = trace_counter
        # the implementation axis rides into every block this runner
        # compiles; eligibility is resolved once (the compensated
        # aggregate keeps the task and prox, only the schedule changes)
        self.implementation = plan.implementation
        self.kernel_loss = (
            require_kernel_loss(task, self.agg_sharded, self.implementation)
            if self.implementation != "xla_fold" else None
        )
        self._blocks: Dict[Tuple, Callable] = {}
        # repeat queries over the same live table skip re-partitioning /
        # re-placing it on the mesh (leaf identity, like Engine._reports;
        # entries pin their leaves so ids cannot be recycled)
        self._placed: Dict[Tuple, Tuple] = {}

    def placed(self, key: Tuple, leaves: Tuple, build: Callable):
        hit = self._placed.get(key)
        if hit is not None:
            return hit[1]
        value = build()
        while len(self._placed) >= 8:
            self._placed.pop(next(iter(self._placed)))
        self._placed[key] = (leaves, value)
        return value

    @property
    def mesh(self):
        return mesh_lib.shard_mesh(self.plan.shard_devices)

    def block(self, mode: str, block_len: int, n_rows: int,
              batch: int = 0) -> Callable:
        key = (mode, block_len, n_rows, batch)
        fn = self._blocks.get(key)
        if fn is None:
            fn = counted_jit(
                build_shard_block(
                    self.agg_sharded, self.mesh,
                    num_shards=self.plan.num_shards,
                    block_len=block_len, mode=mode, n_rows=n_rows,
                    unroll=self.plan.unroll, batch=batch,
                    implementation=self.implementation,
                    kernel_loss=self.kernel_loss,
                ),
                self.trace_counter,
            )
            self._blocks[key] = fn
        return fn

    def batched_block(self, mode: str, block_len: int, n_rows: int,
                      batch: int) -> Callable:
        """Fused-serving variant: a leading query axis of ``batch``
        lanes with per-lane epoch budgets (``repro.engine.serve`` fans
        same-key queries into it, for every ordering)."""
        return self.block(mode, block_len, n_rows, batch=batch)


# ---------------------------------------------------------------------------
# fused batches (B > 1, singleton parallelism)
# ---------------------------------------------------------------------------


def _build_fused(task, agg, prog: EpochProgram, n: int,
                 counter: Dict[str, int]) -> CompiledProgram:
    """Stack B query lanes and compile the ENTIRE multi-epoch run as one
    call: ``lax.scan`` over epochs around a ``vmap`` over lanes, with
    per-lane threefry streams and per-lane epoch budgets. ``run_fn``'s
    contract:

    * mode ``"fused"``: ``run_fn(states, data, keys, budgets)`` — the
      ordering's shuffles (and their rng splits) happen on device
      in-run;
    * mode ``"fixed"``: the epoch stream is prepared once outside
      (``prep_fn`` / stacking) and ``run_fn(states, examples, keys,
      budgets)`` only consumes the per-epoch executor splits.

    ``budgets[B]`` freezes lane i after ``budgets[i]`` epochs (frozen
    lanes' keys keep splitting, but nothing downstream consumes them) —
    the masked-lane fusion that lets heterogeneous-epoch queries share
    one executable. All-equal budgets select the new state everywhere
    and reproduce the homogeneous fused path bit-for-bit."""
    plan = prog.plan
    epochs = prog.epochs
    batch = prog.batch
    shared_table = prog.shared_table
    ordering = plan.ordering
    serial = plan.scheme == "serial"
    raw = build_epoch_fn(task, agg, plan)
    data_axis = None if shared_table else 0
    vperm = jax.vmap(lambda k: jax.random.permutation(k, n))

    def epoch_scan(body, states, keys):
        (states, keys), _ = jax.lax.scan(
            body, (states, keys), jnp.arange(epochs)
        )
        return states, keys

    prep_fn = None
    if serial and ordering in ("shuffle_once", "shuffle_always"):
        # serial fold through the permutation indices: the shuffle is a
        # per-step row gather inside the scan — no lane ever
        # materializes a permuted copy of the table. The rng splits
        # (one for each ordering shuffle, one per executor epoch)
        # replicate the singleton path exactly.
        mode = "fused"
        impl = plan.implementation
        if impl != "xla_fold":
            lane_body = kernel_permuted_lane(
                agg, require_kernel_loss(task, agg, impl)
            )
        else:
            lane_body = permuted_lane(agg, plan.unroll)
        vlane = jax.vmap(lane_body, in_axes=(0, data_axis, 0))
        if ordering == "shuffle_once":

            def run(states, data, keys, budgets):
                keys, psubs = vsplit(keys)  # ShuffleOnce's one split
                perms = vperm(psubs)

                def body(carry, t):
                    st, ks = carry
                    ks, _ = vsplit(ks)  # executor's per-epoch split
                    new = vlane(st, data, perms)
                    st = _lane_select(t < budgets, new, st, axis=0)
                    return (st, ks), None

                return epoch_scan(body, states, keys)

        else:

            def run(states, data, keys, budgets):
                def body(carry, t):
                    st, ks = carry
                    ks, psubs = vsplit(ks)
                    perms = vperm(psubs)
                    ks, _ = vsplit(ks)
                    new = vlane(st, data, perms)
                    st = _lane_select(t < budgets, new, st, axis=0)
                    return (st, ks), None

                return epoch_scan(body, states, keys)

    elif ordering == "shuffle_always":
        # non-serial schemes need materialized example arrays; the
        # per-epoch reshuffle still lives inside the fused run
        mode = "fused"
        vtake = jax.vmap(_take, in_axes=(data_axis, 0))

        def run(states, data, keys, budgets):
            def body(carry, t):
                st, ks = carry
                ks, psubs = vsplit(ks)
                ex = vtake(data, vperm(psubs))
                ks, subs = vsplit(ks)
                new = jax.vmap(raw)(st, ex, subs)
                st = _lane_select(t < budgets, new, st, axis=0)
                return (st, ks), None

            return epoch_scan(body, states, keys)

    else:
        # fixed epoch stream: clustered (any scheme) streams the stored
        # order; non-serial shuffle_once gathers once outside
        mode = "fixed"
        ex_axis = (
            None if (shared_table and ordering == "clustered") else 0
        )
        vraw = jax.vmap(raw, in_axes=(0, ex_axis, 0))

        def run(states, examples, keys, budgets):
            def body(carry, t):
                st, ks = carry
                ks, subs = vsplit(ks)
                new = vraw(st, examples, subs)
                st = _lane_select(t < budgets, new, st, axis=0)
                return (st, ks), None

            return epoch_scan(body, states, keys)

        if ordering == "shuffle_once":
            prep_fn = jax.jit(jax.vmap(
                lambda d, k: _take(d, jax.random.permutation(k, n)),
                in_axes=(data_axis, 0),
            ))

    # when every lane reads the same table object, the objective
    # evaluation broadcasts it instead of stacking B copies
    loss_axes = (0, None) if shared_table else (0, 0)
    return CompiledProgram(
        program=prog, task=task, agg=agg, trace_counter=counter,
        mode=mode,
        run_fn=counted_jit(run, counter, donate_argnums=(0,)),
        prep_fn=prep_fn,
        loss_fn=jax.jit(jax.vmap(task.full_loss, in_axes=loss_axes)),
        init_fn=jax.jit(jax.vmap(agg.initialize)),
    )


# ---------------------------------------------------------------------------
# the compiler
# ---------------------------------------------------------------------------


def build_program(
    task,
    agg,
    prog: EpochProgram,
    *,
    n_examples: int,
    counter: Optional[Dict[str, int]] = None,
) -> CompiledProgram:
    """Lower an :class:`EpochProgram` to its jitted block(s). The ONE
    entry point every driver compiles through — the executor
    (``batch=1``), the sharded subsystem (``parallelism='sharded'``)
    and the serving front-end (``batch>1``) all get their executables
    here, which is what makes a new axis land once instead of four
    times."""
    from repro import obs

    obs.metrics.inc("program.builds")
    # what one transition of the compiled lane body writes to the model
    obs.metrics.set_gauge("program.update_bytes_per_row", agg.update_bytes())
    with obs.span(
        "program.build", axes=prog.plan.axes() if hasattr(prog.plan, "axes")
        else "", batch=prog.batch,
    ):
        return _build_program(
            task, agg, prog, n_examples=n_examples, counter=counter
        )


def _build_program(
    task,
    agg,
    prog: EpochProgram,
    *,
    n_examples: int,
    counter: Optional[Dict[str, int]] = None,
) -> CompiledProgram:
    counter = counter if counter is not None else fresh_counter()
    plan = prog.plan
    impl = plan.implementation
    if impl not in IMPLEMENTATIONS:
        raise ValueError(
            f"unknown implementation {impl!r}; valid: {IMPLEMENTATIONS}"
        )
    if impl != "xla_fold" and plan.scheme != "serial":
        raise ValueError(
            f"implementation={impl!r} lowers the serial lane body; "
            f"scheme={plan.scheme!r} has no kernel form"
        )
    if prog.batch < 1:
        raise ValueError(f"batch must be >= 1, got {prog.batch}")
    if prog.batch == 1 and prog.epochs == 0:
        # driver-paced: the executor loops epochs (and stop rules) on
        # the host around one compiled epoch
        if plan.parallelism == "sharded":
            return CompiledProgram(
                program=prog, task=task, agg=agg, trace_counter=counter,
                runner=ShardedRunner(task, agg, plan, counter),
            )
        if plan.source == "table":
            epoch_fn = build_chunk_epoch_fn(task, agg, plan, counter)
        else:
            # Every non-MRS scheme's state is dead after the epoch call,
            # so the aggregate runs in place (donation). The MRS carry
            # aliases one zero buffer as both reservoirs on epoch 1,
            # which donation forbids, and the swap needs the undonated
            # buffer objects.
            donate = (0,) if plan.scheme != "mrs" else ()
            epoch_fn = counted_jit(
                build_epoch_fn(task, agg, plan), counter,
                donate_argnums=donate,
            )
        return CompiledProgram(
            program=prog, task=task, agg=agg, trace_counter=counter,
            epoch_fn=epoch_fn,
        )
    # fused runs (B lanes; B=1 is a valid single-lane whole-run compile)
    if plan.scheme == "mrs":
        raise ValueError(
            "MRS plans carry per-query reservoirs and cannot be fused"
        )
    if prog.epochs < 1:
        raise ValueError(
            "a fused program compiles its epoch bound into the scan: "
            f"epochs must be >= 1, got {prog.epochs}"
        )
    if plan.parallelism == "sharded":
        if not prog.shared_table:
            raise ValueError(
                "fused sharded batches require one shared table (per-"
                "query segment banks would multiply the partitioned "
                "footprint)"
            )
        # the blocks themselves come from the singleton compile's
        # runner (runner.batched_block) so fused and singleton queries
        # share executables; this program carries the lane-wise
        # init/loss wrappers
        return CompiledProgram(
            program=prog, task=task, agg=agg, trace_counter=counter,
            mode="sharded",
            loss_fn=jax.jit(jax.vmap(task.full_loss, in_axes=(0, None))),
            init_fn=jax.jit(jax.vmap(agg.initialize)),
        )
    return _build_fused(task, agg, prog, n_examples, counter)
