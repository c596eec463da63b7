"""Plan execution behind a compiled-plan cache.

The executor is a *driver* over the one program compiler
(``repro.engine.program``): a chosen ``Plan`` becomes an
``EpochProgram`` (batch=1), ``build_program`` lowers it to a jitted
epoch callable (or a ``ShardedRunner`` of compiled blocks), and the
executable is memoized keyed by (task, task_args, table signature,
plan). Serving many analytics queries per second means the same (task,
shape) pair arrives over and over; a cache hit skips tracing AND XLA
compilation entirely — the epoch function object is reused, so jax's
own jit cache is hit by construction. ``trace_count`` on each
executable counts actual retraces, which the cache test pins to zero
across repeated queries.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import convergence, ordering as ordering_lib
from repro.core.tracecount import counted_jit as _counted_jit  # noqa: F401
from repro.engine import catalog, planner as planner_lib, program as program_lib
from repro.engine import table as table_lib, xla_cache
from repro.engine.program import PERM_STREAM_SALT, build_epoch_fn  # noqa: F401
from repro.engine.query import AnalyticsQuery

_ORDERINGS = {
    "clustered": ordering_lib.Clustered,
    "shuffle_once": ordering_lib.ShuffleOnce,
    "shuffle_always": ordering_lib.ShuffleAlways,
}


@dataclasses.dataclass
class CompiledPlan:
    """A plan lowered to jitted callables for one table signature."""

    key: Tuple
    plan: planner_lib.Plan
    agg: Any
    task: Any
    epoch_fn: Callable  # scheme-specific jitted epoch (or ShardedRunner)
    loss_fn: Optional[Callable]
    trace_counter: Dict[str, int]
    # the objective evaluation retraces on its own cadence (stop rules
    # call it every epoch); counted separately so ``trace_count`` stays a
    # pure epoch-executable observable
    loss_trace_counter: Dict[str, int]

    @property
    def trace_count(self) -> int:
        return self.trace_counter["traces"]

    @property
    def loss_trace_count(self) -> int:
        return self.loss_trace_counter["traces"]


def device_key() -> Tuple[int, str, str]:
    """The hardware a plan was probed on: (device count, platform,
    device_kind). Plans, their probed kernel and fold rates and their
    mesh-probed shard placements are only valid there, so a plan cache
    copied from another machine (a CPU run's ``.plan_cache/`` next to a
    chip run) must miss."""
    dev = jax.local_devices()[0]
    return (jax.local_device_count(), dev.platform, dev.device_kind)


def _fresh_stats() -> Dict[str, int]:
    return {
        "plan_cache_hits": 0,
        "plan_cache_misses": 0,
        "plans_computed": 0,  # planner actually ran (vs memo/disk hit)
        "plan_disk_hits": 0,
    }


class Engine:
    """The unified analytics engine: query -> plan -> cached execute.

    ``plan_store`` (optional) is a persistent plan cache — an object with
    ``load(plan_key, query) -> PlanReport | None`` and
    ``store(plan_key, query, report)`` (see ``repro.engine.serve.PlanStore``
    for the on-disk JSON implementation). A fresh process pointed at a
    populated store warm-starts: it re-probes and re-plans nothing."""

    def __init__(self, plan_store=None):
        self._compiled: Dict[Tuple, CompiledPlan] = {}
        # key -> (pinned data leaves, report); see explain()
        self._reports: Dict[Tuple, Tuple] = {}
        self.plan_store = plan_store
        self.stats = _fresh_stats()
        # compiled executables survive process restarts alongside the
        # PlanStore's plans (JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache)
        xla_cache.maybe_enable()

    # -- planning ---------------------------------------------------------

    def _aggregate_for(self, query: AnalyticsQuery):
        from repro.core import uda as uda_lib

        spec = catalog.get(query.task)
        # a stated step replaces the catalog's alpha0 here, the one place
        # every driver (executor, serve, shard, probes) builds its aggregate
        args, alpha0 = catalog.stated_step(query.task_args)
        if spec.derive_args is not None:
            args.update(spec.derive_args(args, query.n_examples))
        task = spec.make_task(**args)
        agg = uda_lib.IGDAggregate(
            task,
            spec.schedule(query.n_examples, alpha0),
            prox=spec.prox(task),
        )
        return spec, task, agg

    def explain(self, query: AnalyticsQuery) -> planner_lib.PlanReport:
        """Plan the query; memoized on the live table + query knobs.

        The table component of the key uses leaf identity (jax arrays
        are immutable, so a live leaf with the same id IS the same data;
        a stored ``Table`` handle is itself the identity), NOT just
        shapes: a different table of the same shape may have different
        statistics and must be re-planned. The serving hot path — the
        same table queried repeatedly — hits."""
        leaves = tuple(jax.tree.leaves(query.data))
        plan_key = self._query_plan_key(query)
        key = (plan_key, tuple(id(x) for x in leaves))
        hit = self._reports.get(key)
        if hit is not None:
            return hit[1]
        report = None
        if self.plan_store is not None:
            report = self.plan_store.load(plan_key, query)
            if report is not None:
                self.stats["plan_disk_hits"] += 1
        if report is None:
            _, _, agg = self._aggregate_for(query)
            report = planner_lib.plan(query, agg)
            self.stats["plans_computed"] += 1
            if self.plan_store is not None:
                self.plan_store.store(plan_key, query, report)
        # pin the leaves so a live memo entry's ids cannot be recycled
        # for a different table; bound the memo so pins don't accumulate
        while len(self._reports) >= 128:
            self._reports.pop(next(iter(self._reports)))
        self._reports[key] = (leaves, report)
        return report

    @staticmethod
    def _query_plan_key(query: AnalyticsQuery) -> Tuple:
        return query.cache_key_fields() + (
            query.epochs,
            query.memory_budget_bytes,
            tuple(sorted(query.hints.items())),
        ) + device_key()

    # -- compilation cache ------------------------------------------------

    def _compile(
        self, query: AnalyticsQuery, plan: planner_lib.Plan
    ) -> CompiledPlan:
        key = query.cache_key_fields() + (plan,)
        hit = self._compiled.get(key)
        if hit is not None:
            self.stats["plan_cache_hits"] += 1
            return hit
        self.stats["plan_cache_misses"] += 1

        with obs.span("engine.compile", task=query.task, axes=plan.axes()):
            t0 = time.perf_counter()
            _, task, agg = self._aggregate_for(query)
            counter = {"traces": 0}
            loss_counter = {"traces": 0}
            compiled_prog = program_lib.build_program(
                task, agg, program_lib.EpochProgram(plan=plan),
                n_examples=query.n_examples, counter=counter,
            )
            epoch_fn = (
                compiled_prog.runner
                if plan.parallelism == "sharded"
                else compiled_prog.epoch_fn
            )
            loss_fn = _counted_jit(
                lambda model, data: task.full_loss(model, data), loss_counter
            )
            obs.metrics.observe("engine.compile_s", time.perf_counter() - t0)
        compiled = CompiledPlan(
            key=key, plan=plan, agg=agg, task=task,
            epoch_fn=epoch_fn, loss_fn=loss_fn, trace_counter=counter,
            loss_trace_counter=loss_counter,
        )
        self._compiled[key] = compiled
        return compiled

    def cache_info(self) -> Dict[str, int]:
        return dict(self.stats, compiled_plans=len(self._compiled))

    def clear_cache(self) -> None:
        self._compiled.clear()
        self._reports.clear()
        self.stats = _fresh_stats()

    # -- execution --------------------------------------------------------

    def run(
        self,
        query: AnalyticsQuery,
        *,
        plan: Optional[planner_lib.Plan] = None,
    ) -> "EngineResult":
        """Plan (unless ``plan`` forces one), compile-or-hit, execute."""
        report = None
        if plan is None:
            report = self.explain(query)
            plan = report.chosen
        with obs.span("engine.run", task=query.task, axes=plan.axes()) as sp:
            compiled = self._compile(query, plan)
            sp.set(alpha0=compiled.agg.step_size.alpha0)
            return _execute(compiled, query, report)

    # -- EXPLAIN ANALYZE ---------------------------------------------------

    def explain_analyze(self, query: AnalyticsQuery) -> obs.DriftReport:
        """Run the chosen plan under the span tracer and diff the cost
        model against the walls it actually produced, per composed axis.

        The predicted side re-prices the plan via
        ``planner.cost_components`` at the epoch count the run actually
        executed (a converged run stops early; the plan-time estimate
        prices the full budget — epoch-count error is convergence
        modeling, not calibration drift, and must not pollute the
        per-second drift signal). The measured side maps the same axes
        onto the run's walls: ordering <- the shuffle/placement wall,
        parallelism <- the epoch fold wall, source <- the
        ``engine.materialize`` span (Table.resolve), batching <- zero on
        this single-query path (fused lanes are priced and measured on
        the serving path). Loss evaluation is excluded from both sides —
        the model never priced it. The report persists next to the plan
        in the PlanStore (``load_analysis`` reads it back), so a fresh
        process can detect stale calibration before trusting a stored
        plan."""
        report = self.explain(query)
        plan = report.chosen
        with obs.tracing() as rec:  # restores the caller's tracer state
            res = self.run(query)
        materialize_s = rec.total("engine.materialize")
        attribution = obs.attribution.attribute(
            rec.spans, root_name="engine.run"
        )

        comps, _ = planner_lib.cost_components(
            plan, query, report.calibration, float(max(res.epochs, 1)),
        )
        # serial singleton plans carry their lane-body compute on the
        # implementation axis (cost_components splits the same total, it
        # doesn't double-count); every other scheme keeps the epoch fold
        # wall under parallelism
        impl_axis = (
            plan.parallelism != "sharded" and plan.scheme == "serial"
        )
        rows = (
            obs.AxisCost(
                "ordering", comps["ordering"], res.shuffle_seconds,
                "shuffle/placement wall (EngineResult.shuffle_seconds)",
            ),
            obs.AxisCost(
                "parallelism", comps["parallelism"],
                0.0 if impl_axis else res.gradient_seconds,
                "lane body measured on the implementation axis"
                if impl_axis
                else "epoch fold wall (EngineResult.gradient_seconds)",
            ),
            obs.AxisCost(
                "batching", 0.0, 0.0,
                "single-query run (B=1); fused lanes are priced on the "
                "serving path",
            ),
            obs.AxisCost(
                "source", comps["source"], materialize_s,
                "engine.materialize span (Table.resolve)",
            ),
            obs.AxisCost(
                "implementation", comps.get("implementation", 0.0),
                res.gradient_seconds if impl_axis else 0.0,
                f"epoch fold wall of the {plan.implementation} lane body "
                "(EngineResult.gradient_seconds)"
                if impl_axis
                else "lane body measured on the parallelism axis",
            ),
        )
        analysis = obs.DriftReport(
            axes=plan.axes(),
            plan=plan.to_dict(),
            rows=rows,
            epochs_run=res.epochs,
            predicted_total_s=sum(r.predicted_s for r in rows),
            measured_total_s=sum(r.measured_s for r in rows),
            attribution=(
                attribution.to_dict() if attribution is not None else None
            ),
        )
        # surface the verdict as gauges so SLO rules (and /metrics
        # scrapes) can watch calibration staleness without re-analyzing
        obs.metrics.set_gauge("engine.drift_ratio", analysis.drift)
        obs.metrics.set_gauge(
            "engine.calibration_stale", 1.0 if analysis.stale else 0.0
        )
        if self.plan_store is not None:
            self.plan_store.store_analysis(
                self._query_plan_key(query), query, analysis
            )
        return analysis

    def load_analysis(
        self, query: AnalyticsQuery
    ) -> Optional[obs.DriftReport]:
        """The last persisted EXPLAIN ANALYZE for this query's plan key,
        if the store holds one (e.g. written by a previous process)."""
        if self.plan_store is None:
            return None
        return self.plan_store.load_analysis(
            self._query_plan_key(query), query
        )


@dataclasses.dataclass
class EngineResult:
    model: Any
    losses: List[float]
    epochs: int
    converged: bool
    plan: planner_lib.Plan
    report: Optional[planner_lib.PlanReport]
    shuffle_seconds: float
    gradient_seconds: float
    trace_count: int  # retraces of this query's epoch executable, cumulative
    loss_trace_count: int = 0  # retraces of the objective evaluation
    batch_size: int = 1  # queries fused into the epoch call that ran this

    def describe(self) -> str:
        # losses can be empty: epochs=0, or a run that never evaluated
        # the objective (no stop rule and no loss_fn)
        loss = f"loss={self.losses[-1]:.6g}" if self.losses else "loss=n/a"
        head = f"{self.epochs} epochs, {loss}, converged={self.converged}"
        body = self.report.describe() if self.report else self.plan.describe()
        return f"{head}\n{body}"


def _eval_loss(compiled: CompiledPlan, agg, state, loss_data) -> float:
    """One objective evaluation, timed into ``engine.loss_s`` (kept out
    of the per-epoch fold walls — the cost model never prices it)."""
    t0 = time.perf_counter()
    with obs.span("engine.loss"):
        value = float(compiled.loss_fn(agg.terminate(state), loss_data))
    obs.metrics.observe("engine.loss_s", time.perf_counter() - t0)
    return value


def _execute(
    compiled: CompiledPlan,
    query: AnalyticsQuery,
    report: Optional[planner_lib.PlanReport],
) -> EngineResult:
    plan = compiled.plan
    if plan.parallelism == "sharded":
        from repro.engine import shard as shard_lib

        return shard_lib.execute(compiled, query, report)
    agg = compiled.agg
    data = query.data
    stored = table_lib.is_stored_table(data)
    streaming = plan.source == "table"
    if streaming and not stored:
        raise ValueError(
            "plan.source='table' needs a stored Table (duck-typed: "
            "is_stored_table); got an in-memory pytree"
        )
    if stored and not streaming:
        # the plan chose random access (shuffle orderings, segmented
        # layouts): materialize through the one resolve seam
        t0 = time.perf_counter()
        with obs.span("engine.materialize", task=query.task):
            data = table_lib.resolve(data)
        obs.metrics.observe("engine.materialize_s", time.perf_counter() - t0)
    # the objective is a full-table aggregate either way (Table.arrays()
    # memoizes, so streamed runs pay this once, and only if a loss is
    # ever evaluated)
    loss_data = table_lib.resolve(query.data) if stored else data
    n = query.n_examples
    rng, perm_rng = program_lib.seed_streams(query.seed)
    ordering = _ORDERINGS[plan.ordering]()
    if query.target_loss is not None:
        stop = lambda losses, epoch: bool(  # noqa: E731
            losses and losses[-1] <= query.target_loss
        )
    elif query.tolerance:
        stop = convergence.RelativeLossDrop(query.tolerance)
    else:
        stop = None

    state = agg.initialize(rng)
    if plan.scheme == "mrs":
        zero_buf = jax.tree.map(
            lambda x: jnp.zeros((plan.mrs_buffer,) + x.shape[1:], x.dtype),
            data,
        )
        carry = (state, zero_buf, zero_buf, jnp.bool_(False))

    losses: List[float] = []
    shuffle_s = 0.0
    grad_s = 0.0
    converged = False
    epoch = 0
    for epoch in range(1, query.epochs + 1):
        # engine.sync wraps each wait for the device, so on a profiler's
        # timeline the device's idle time inside engine.epoch but outside
        # engine.order and engine.sync is the host's own work per epoch
        with obs.span("engine.epoch", index=epoch):
            t0 = time.perf_counter()
            if streaming:
                examples = data  # the chunk stream IS the stored order
            else:
                with obs.span("engine.order"):
                    examples, perm_rng = ordering.order(
                        data, n, epoch, perm_rng
                    )
                    with obs.span("engine.sync"):
                        jax.block_until_ready(examples)
            t1 = time.perf_counter()
            perm_rng, sub = jax.random.split(perm_rng)
            if plan.scheme == "mrs":
                state, buf_a, buf_b, _ = compiled.epoch_fn(
                    carry, examples, sub
                )
                # swap: the memory worker cycles last epoch's reservoir
                carry = (state, buf_b, buf_a, jnp.bool_(True))
            else:
                state = compiled.epoch_fn(state, examples, sub)
            with obs.span("engine.sync"):
                jax.block_until_ready(state)
            t2 = time.perf_counter()
        shuffle_s += t1 - t0
        grad_s += t2 - t1
        obs.metrics.observe("engine.epoch.shuffle_s", t1 - t0)
        obs.metrics.observe("engine.epoch.grad_s", t2 - t1)
        # A stop rule needs the per-epoch objective; without one, a single
        # evaluation after the last epoch suffices (full_loss scans the
        # whole table — not free on the serving path).
        if stop is not None and compiled.loss_fn is not None:
            losses.append(_eval_loss(compiled, agg, state, loss_data))
            if stop(losses, epoch):
                converged = True
                break
    if stop is None and compiled.loss_fn is not None and epoch:
        losses.append(_eval_loss(compiled, agg, state, loss_data))

    return EngineResult(
        model=agg.terminate(state),
        losses=losses,
        epochs=epoch,
        converged=converged,
        plan=plan,
        report=report,
        shuffle_seconds=shuffle_s,
        gradient_seconds=grad_s,
        trace_count=compiled.trace_count,
        loss_trace_count=compiled.loss_trace_count,
    )
