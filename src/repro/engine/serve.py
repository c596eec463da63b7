"""repro.engine.serve — the high-QPS serving front-end.

A database serves many concurrent analytics queries, not one script at a
time. This layer models that multi-tenant reality on top of the unified
engine with three mechanisms:

* **Admission control** (``ServingEngine.submit``): a bounded queue with
  a per-task depth limit. Overload sheds cleanly — a rejected query gets
  an immediate ``Ticket`` with ``accepted=False`` and a reason
  (``queue_full`` / ``task_limit``) instead of unbounded queueing.

* **Cross-query batching** (``ServingEngine.pump``): queued queries that
  share a *fused key* — same ``(task, task_args, table signature)``
  (the executor's cache key fields) and same chosen plan — are stacked
  along a new query axis and the ENTIRE multi-epoch run executes as one
  compiled call, built by the one program compiler
  (``repro.engine.program.build_program``: ``lax.scan`` over epochs
  around a ``vmap`` over queries). Queries that differ ONLY in their
  epoch budget still fuse: every fused run takes per-lane budgets and
  freezes a lane once its budget is spent (masked-lane fusion), so N
  heterogeneous fits of the same shape cost ~1 executable instead of N.
  Per-query rng streams are batched threefry ops (bit-identical to the
  singleton executor's), shuffle orderings fold through permutation
  indices in-scan instead of materializing permuted copies, and the
  batched executable's scan unroll is re-probed on a stacked slab
  (``probes.probe_batch_unroll``). Sharded plans fuse too — for EVERY
  ordering — by riding a query axis inside the sharded blocks
  (``runner.batched_block``); they require one shared table. Queries
  with an early-stop rule (``tolerance``/``target_loss``), an MRS plan,
  or a stored-table source keep per-query control flow and fall back to
  singleton ``Engine.run``.

* **Persistent plan cache** (``PlanStore``): the planner's artifacts —
  chosen plan, full EXPLAIN report, micro-probe calibration — persisted
  as one JSON file per plan-cache key. A fresh process pointed at a
  populated store warm-starts: ``explain`` loads the report and seeds
  the probe cache, so it re-probes and re-plans nothing (the XLA
  executables themselves still compile per process; what the store
  eliminates is every *measurement* on the hot path).

Typical use::

    from repro.engine import serve

    srv = serve.ServingEngine(serve.ServeConfig(cache_dir=".plan_cache"))
    # NOTE: only fixed-epoch queries fuse — build them with
    # tolerance=0.0 and no target_loss. AnalyticsQuery's DEFAULT
    # tolerance (1e-3) is an early-stop rule, which forces the
    # per-query singleton path (stats["singleton_queries"] shows it).
    tickets = [srv.submit(q) for q in queries]
    srv.drain()
    for t in tickets:
        print(t.result.describe() if t.accepted else t.reject_reason)
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs import flight as flight_lib, slo as slo_lib
from repro.engine import executor, planner as planner_lib
from repro.engine import probes, program as program_lib
from repro.engine import table as table_lib
from repro.engine.program import vseed as _vseed, vsplit as _vsplit
from repro.engine.query import AnalyticsQuery

# Bump when the on-disk entry layout (or anything the planner persists)
# changes shape: version-mismatched entries are ignored and rewritten.
# v2: Plan grew the parallelism axis; Calibration grew the mesh-probed
# segmented/sharded cost tables (repro.engine.shard).
# (The EpochProgram refactor added Plan.source and PlanReport.axes with
# backward-compatible defaults — v2 entries still load.)
# v3: Plan grew the implementation axis (fused-IGD kernel lanes) and
# Calibration grew impl_per_row; old entries would silently re-plan the
# kernel choice from stale constants, so they are invalidated.
# v4: the planner dropped the simulated shared-memory scheme (two Plan
# fields) and the mean-gradient tile kernel (a rate in impl_per_row); a
# v3 entry is re-planned, not loaded.
FORMAT_VERSION = 4

REJECT_QUEUE_FULL = "queue_full"
REJECT_TASK_LIMIT = "task_limit"


# ---------------------------------------------------------------------------
# persistent plan cache
# ---------------------------------------------------------------------------


class PlanStore:
    """On-disk plan cache: ``<root>/plan_<sha256(plan_key)>.json``.

    Each entry holds {version, key repr, table content fingerprint,
    serialized PlanReport (plan + calibrated cost table + full candidate
    ranking)}. Invalidation is structural: a version bump, a key-repr
    mismatch (hash collision / foreign file) or a fingerprint mismatch
    (same-shaped but different table, whose statistics may differ) all
    read as a miss, and the next ``store`` overwrites the entry.
    Writes are atomic (tmp file + rename) so a crashed process never
    leaves a torn entry."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def size(self) -> int:
        """Live plan-entry count (analysis/tmp/parked files excluded) —
        registered as the ``serve.plan_store_entries`` callback gauge so
        snapshots see the store grow."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return 0
        return sum(
            1 for n in names
            if n.startswith("plan_") and n.endswith(".json")
            and not n.endswith(".analyze.json")
        )

    def _path(self, plan_key: Tuple) -> str:
        digest = hashlib.sha256(repr(plan_key).encode()).hexdigest()[:32]
        return os.path.join(self.root, f"plan_{digest}.json")

    def load(
        self, plan_key: Tuple, query: AnalyticsQuery
    ) -> Optional[planner_lib.PlanReport]:
        try:
            with open(self._path(plan_key)) as f:
                entry = json.load(f)
        except (OSError, ValueError):
            return None
        if (
            entry.get("version") != FORMAT_VERSION
            or entry.get("key") != repr(plan_key)
            or entry.get("fingerprint") != query.content_fingerprint()
        ):
            return None
        try:
            report = planner_lib.PlanReport.from_dict(entry["report"])
        except (KeyError, TypeError, ValueError):
            return None
        # seed the probe cache: even a re-plan (e.g. different epochs
        # against the same table) measures nothing in this process
        probes.seed(query.cache_key_fields(), report.calibration)
        return report

    def store(
        self, plan_key: Tuple, query: AnalyticsQuery,
        report: planner_lib.PlanReport,
    ) -> None:
        self._write(
            self._path(plan_key), plan_key, query,
            {"report": report.to_dict()},
        )

    # -- EXPLAIN ANALYZE persistence --------------------------------------
    # The drift report lives NEXT TO the plan entry (same digest, its own
    # file) so the last measured run travels with the stored plan: a
    # fresh process can check calibration staleness before trusting it.

    def _analysis_path(self, plan_key: Tuple) -> str:
        return self._path(plan_key)[: -len(".json")] + ".analyze.json"

    def load_analysis(
        self, plan_key: Tuple, query: AnalyticsQuery
    ) -> Optional[obs.DriftReport]:
        try:
            with open(self._analysis_path(plan_key)) as f:
                entry = json.load(f)
        except (OSError, ValueError):
            return None
        if (
            entry.get("version") != FORMAT_VERSION
            or entry.get("key") != repr(plan_key)
            or entry.get("fingerprint") != query.content_fingerprint()
        ):
            return None
        try:
            return obs.DriftReport.from_dict(entry["analysis"])
        except (KeyError, TypeError, ValueError):
            return None

    def store_analysis(
        self, plan_key: Tuple, query: AnalyticsQuery,
        analysis: obs.DriftReport,
    ) -> None:
        self._write(
            self._analysis_path(plan_key), plan_key, query,
            {"analysis": analysis.to_dict()},
        )

    def _write(
        self, path: str, plan_key: Tuple, query: AnalyticsQuery,
        payload: dict,
    ) -> None:
        entry = {
            "version": FORMAT_VERSION,
            "key": repr(plan_key),
            "fingerprint": query.content_fingerprint(),
            **payload,
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(entry, f, indent=1)
            os.replace(tmp, path)
        except OSError:
            # persistence is an optimization: a full/read-only/deleted
            # cache dir must degrade to planning without it, not turn
            # every new-plan-key query into a serving error
            try:
                os.unlink(tmp)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_queue: int = 64  # bounded admission queue (total queued queries)
    max_per_task: int = 32  # per-task queue-depth limit
    max_batch: int = 8  # queries fused into one vmapped epoch call
    cache_dir: Optional[str] = None  # persistent plan cache root
    # bound on retained fused executables: each entry holds compiled XLA
    # code per (query key, plan, batch size, epoch bound), so a long-
    # running server seeing many burst sizes must not accumulate them
    # unboundedly
    max_compiled_batches: int = 32
    # always-on flight recorder: the serving engine installs a span ring
    # of this many completed spans (0 opts out) so the last N spans are
    # dumpable post-hoc — and land in every SLO incident file
    flight_capacity: int = 256
    # declarative SLOs (repro.obs.slo.SLORule tuple; None = unmonitored)
    # evaluated between pump groups at slo_interval_s cadence; breaches
    # dump the flight ring to incident_dir (default:
    # <cache_dir>/incidents when a cache_dir is configured)
    slo_rules: Optional[Tuple] = None
    slo_interval_s: float = 1.0
    incident_dir: Optional[str] = None


_UNSET = object()  # sentinel: a ticket's batch key may legitimately be None


@dataclasses.dataclass(eq=False)  # identity eq: the queue removes by ticket
class Ticket:
    """One submitted query's handle: admission verdict, then the result."""

    query: AnalyticsQuery
    accepted: bool
    reject_reason: Optional[str] = None
    submit_s: float = 0.0
    # when a pump took the ticket off the queue (queue wait = dequeue_s -
    # submit_s); None until then, and for a ticket refused at admission
    dequeue_s: Optional[float] = None
    done_s: Optional[float] = None
    result: Optional[executor.EngineResult] = None
    # a query that failed planning/execution completes with the error
    # recorded instead of killing the server loop (result stays None)
    error: Optional[str] = None
    # pump() memoizes the fused key here so a ticket is planned at
    # most once while queued (a >128-table queue would otherwise thrash
    # the engine's explain memo and replan per pump scan)
    batch_key_cache: Any = _UNSET

    @property
    def done(self) -> bool:
        return self.done_s is not None

    @property
    def latency_s(self) -> Optional[float]:
        """Queue wait + execution (submit -> completion)."""
        return None if self.done_s is None else self.done_s - self.submit_s


def _pump_record(
    wall: float, cpu: float, lanes: int, depth: int, max_wait: float,
    jax0: Tuple[int, ...], spans: List[dict],
) -> Dict[str, Any]:
    """``ServingEngine.slowest_pump``: one pump's wall and main-thread
    CPU seconds, its lanes, the queue depth and worst queue wait when it
    took its group, the JAX compile events during it (deltas of
    ``obs.compile_counts``), and (name, seconds) of the spans the flight
    ring recorded inside it, in completion order."""
    traces, lowerings, compiles, loads = (
        b - a for a, b in zip(jax0, obs.compile_counts())
    )
    return {
        "wall_s": wall,
        "cpu_s": cpu,
        "lanes": lanes,
        "queue_depth": depth,
        "max_wait_s": max_wait,
        "traces": traces,
        "lowerings": lowerings,
        "compiles": compiles,
        "cache_loads": loads,
        "spans": [(s["name"], s["dur"]) for s in spans],
    }


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------


class ServingEngine:
    """Admission control + cross-query batching over one ``Engine``.

    Single-pump execution model: ``submit`` only enqueues (admission is
    O(1) and never blocks on planning); ``pump`` takes the queue head,
    fuses every compatible queued query with it (up to ``max_batch``),
    and executes the group — so "concurrency" is the fused batch, which
    is the honest model on a single accelerator. ``drain`` pumps until
    the queue is empty."""

    def __init__(
        self,
        config: ServeConfig = ServeConfig(),
        engine: Optional[executor.Engine] = None,
    ):
        if engine is None:
            store = PlanStore(config.cache_dir) if config.cache_dir else None
            engine = executor.Engine(plan_store=store)
        elif config.cache_dir and engine.plan_store is None:
            # an explicitly passed engine still honors the cache_dir knob
            # (silently dropping it would re-probe on every restart —
            # the exact cost the knob exists to eliminate)
            engine.plan_store = PlanStore(config.cache_dir)
        self.engine = engine
        self.config = config
        self._queue: collections.deque = collections.deque()
        self._queued_per_task: collections.Counter = collections.Counter()
        self._batched: Dict[Tuple, program_lib.CompiledProgram] = {}
        # operational telemetry: the always-on flight ring, the live
        # queue-depth / plan-store-size callback gauges (a snapshot or a
        # /metrics scrape sees them without calling into the engine),
        # and the SLO monitor pump() evaluates on its cadence
        if config.flight_capacity:
            flight_lib.enable(config.flight_capacity)
        obs.metrics.gauge("serve.queue_depth", fn=lambda: len(self._queue))
        store = self.engine.plan_store
        if store is not None and hasattr(store, "size"):
            obs.metrics.gauge("serve.plan_store_entries", fn=store.size)
        self.slo: Optional[slo_lib.SLOMonitor] = None
        if config.slo_rules:
            incident_dir = config.incident_dir
            if incident_dir is None and config.cache_dir:
                incident_dir = os.path.join(config.cache_dir, "incidents")
            self.slo = slo_lib.SLOMonitor(
                config.slo_rules,
                interval_s=config.slo_interval_s,
                incident_dir=incident_dir,
            )
        self.stats = {
            "accepted": 0,
            "rejected": 0,
            "shed_queue_full": 0,  # rejected: total queue bound
            "shed_task_limit": 0,  # rejected: per-task depth limit
            "batches": 0,
            "batched_queries": 0,
            "fused_lanes": 0,  # lanes that rode a fused (batch>1) call
            "masked_batches": 0,  # fused groups with heterogeneous epochs
            "singleton_queries": 0,
            "failed_queries": 0,
        }
        # the longest pump so far (see pump()), also live in /snapshot as
        # the serve.slowest_pump gauge
        self.slowest_pump: Optional[Dict[str, Any]] = None
        obs.metrics.gauge("serve.slowest_pump", fn=lambda: self.slowest_pump)

    # -- admission --------------------------------------------------------

    def submit(self, query: AnalyticsQuery) -> Ticket:
        now = time.perf_counter()
        if len(self._queue) >= self.config.max_queue:
            self.stats["rejected"] += 1
            self.stats["shed_queue_full"] += 1
            obs.metrics.inc("serve.shed.queue_full")
            return Ticket(query, False, REJECT_QUEUE_FULL, submit_s=now)
        if self._queued_per_task[query.task] >= self.config.max_per_task:
            self.stats["rejected"] += 1
            self.stats["shed_task_limit"] += 1
            obs.metrics.inc("serve.shed.task_limit")
            return Ticket(query, False, REJECT_TASK_LIMIT, submit_s=now)
        ticket = Ticket(query, True, submit_s=now)
        self._queue.append(ticket)
        self._queued_per_task[query.task] += 1
        self.stats["accepted"] += 1
        obs.metrics.inc("serve.accepted")
        return ticket

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    # -- batching ---------------------------------------------------------

    def _batch_key(self, query: AnalyticsQuery) -> Optional[Tuple]:
        """The fused key, or None when the query must run solo.

        Early-stop queries (tolerance / target_loss) need per-query stop
        rules; MRS plans carry per-query reservoirs; stored tables are a
        chunk stream, not a stackable pytree. All keep the singleton
        path (which also serves them from the compiled-plan cache).
        Note ``epochs`` is NOT part of the key: queries that differ only
        in their epoch budget fuse via per-lane masks."""
        if query.target_loss is not None or query.tolerance:
            return None
        if query.epochs < 1:
            return None  # nothing to fuse; parity: no objective either
        if query.memory_budget_bytes is not None:
            # fusing stacks up to max_batch tables into one allocation —
            # B× the footprint the planner budgeted as feasible; honor
            # the budget by keeping budgeted queries singleton
            return None
        if table_lib.is_stored_table(query.data):
            return None
        try:
            plan = self.engine.explain(query).chosen
        except Exception:  # unplannable: let the singleton path report it
            return None
        if plan.scheme == "mrs":
            return None
        return (query.cache_key_fields(), plan)

    def _ticket_key(self, ticket: Ticket) -> Optional[Tuple]:
        if ticket.batch_key_cache is _UNSET:
            ticket.batch_key_cache = self._batch_key(ticket.query)
        return ticket.batch_key_cache

    def pump(self) -> int:
        """Serve the queue head (plus everything batchable with it).
        Returns the number of queries completed.

        A pump longer than every one before it replaces
        ``slowest_pump``, the record that names which side a stall was
        on: its wall against the main thread's CPU time (host work), the
        JAX traces, lowerings, compiles and cache loads it waited for,
        and the spans the flight ring recorded inside it."""
        if not self._queue:
            return 0
        w0, c0 = time.perf_counter(), time.thread_time()
        jax0 = obs.compile_counts()
        ring = flight_lib.get()
        mark = ring.appended if ring is not None else 0
        depth = len(self._queue)
        head = self._queue.popleft()
        self._queued_per_task[head.query.task] -= 1
        group = [head]
        key = self._ticket_key(head)
        if key is not None and self.config.max_batch > 1:
            # stop scanning once the batch is full, and never force
            # planning (_ticket_key -> explain -> micro-probes) on a
            # ticket whose cheap key prefix already rules fusion out —
            # a heterogeneous queue must not pay the whole queue's
            # planning inside the head query's latency
            matches = []
            for t in self._queue:
                if len(matches) >= self.config.max_batch - 1:
                    break
                if t.query.cache_key_fields() != key[0]:
                    continue
                if self._ticket_key(t) == key:
                    matches.append(t)
            for t in matches:
                self._queue.remove(t)
                self._queued_per_task[t.query.task] -= 1
            group.extend(matches)
        dequeued = time.perf_counter()
        for t in group:
            t.dequeue_s = dequeued
            obs.metrics.observe(
                f"serve.queue_wait_s.{t.query.task}", dequeued - t.submit_s
            )
        # the group span is what tail-latency attribution decomposes:
        # admission wait is not a span, so the pump stamps the group's
        # worst wait as an attribute for the queue_wait phase
        max_wait = max(dequeued - t.submit_s for t in group)

        # one bad query must not take the server loop (or the rest of the
        # queue) down with it: failures complete the ticket with an error
        with obs.span(
            "serve.pump", batch=len(group), queue_wait_s=max_wait
        ):
            try:
                if len(group) == 1:
                    head.result = self.engine.run(head.query)
                    head.done_s = time.perf_counter()
                    self.stats["singleton_queries"] += 1
                elif self._run_batch(group, key[1]):
                    self.stats["batches"] += 1
                    self.stats["batched_queries"] += len(group)
                    self.stats["fused_lanes"] += len(group)
                    obs.metrics.inc("serve.fused_lanes", len(group))
                    if len({t.query.epochs for t in group}) > 1:
                        self.stats["masked_batches"] += 1
                else:
                    # the group declined fusion at run time (sharded plan
                    # over distinct tables): served singleton, still done
                    self.stats["singleton_queries"] += len(group)
            except Exception as e:  # noqa: BLE001
                now = time.perf_counter()
                errored = 0
                for t in group:
                    if t.done_s is None:
                        t.error = f"{type(e).__name__}: {e}"
                        t.done_s = now
                        errored += 1
                self.stats["failed_queries"] += errored
                # tickets already served (the sharded distinct-table
                # fallback completes them one by one) are successes, not
                # casualties
                self.stats["singleton_queries"] += len(group) - errored
        for t in group:
            if t.done_s is not None and t.error is None:
                obs.metrics.observe(
                    f"serve.latency_s.{t.query.task}", t.done_s - t.submit_s
                )
        # SLO cadence: between groups, never mid-batch — monitoring must
        # not sit inside the fused call's wall
        if self.slo is not None:
            self.slo.maybe_evaluate()
        wall = time.perf_counter() - w0
        if self.slowest_pump is None or wall > self.slowest_pump["wall_s"]:
            self.slowest_pump = _pump_record(
                wall, time.thread_time() - c0, len(group), depth, max_wait,
                jax0, ring.since(mark) if ring is not None else [],
            )
        return len(group)

    def drain(self) -> int:
        """Pump until the queue is empty; returns queries completed."""
        total = 0
        while True:
            done = self.pump()
            if not done:
                return total
            total += done

    # -- batched execution ------------------------------------------------

    @staticmethod
    def _timed_phases(assemble, execute) -> Tuple[Any, Any, float, float]:
        """One timing discipline for both fused paths: run ``assemble``
        (input staging — stacking/placement/permutation) then ``execute``
        (the fused epochs), each blocked-until-ready under its own obs
        span, and feed the serve.* wall histograms. Returns
        ``(assembled, executed, assemble_s, execute_s)``."""
        t0 = time.perf_counter()
        with obs.span("serve.assemble"):
            assembled = assemble()
            with obs.span("engine.sync"):
                jax.block_until_ready(assembled)
        t1 = time.perf_counter()
        with obs.span("serve.execute"):
            executed = execute(assembled)
            with obs.span("engine.sync"):
                jax.block_until_ready(executed)
        t2 = time.perf_counter()
        obs.metrics.observe("serve.assembly_s", t1 - t0)
        obs.metrics.observe("serve.execute_s", t2 - t1)
        return assembled, executed, t1 - t0, t2 - t1

    def _finish_group(
        self, tickets: List[Ticket], models, losses,
        plan: planner_lib.Plan, *, shuffle_s: float, grad_s: float,
        trace_count: int,
    ) -> None:
        """Per-ticket completion shared by both fused paths: slice lane
        ``i`` out of the stacked models/losses and stamp an
        ``EngineResult`` whose walls are amortized over the batch (the
        whole group paid them once)."""
        b = len(tickets)
        done = time.perf_counter()
        for i, t in enumerate(tickets):
            t.result = executor.EngineResult(
                model=jax.tree.map(lambda x: x[i], models),
                losses=[float(losses[i])],
                epochs=t.query.epochs,
                converged=False,
                plan=plan,
                report=None,
                shuffle_seconds=shuffle_s / b,
                gradient_seconds=grad_s / b,
                trace_count=trace_count,
                batch_size=b,
            )
            t.done_s = done

    def _batched_put(self, key: Tuple, compiled) -> None:
        """Retain a fused executable, evicting FIFO past the bound (each
        entry holds compiled XLA code — a long-running server seeing many
        burst shapes must not accumulate them unboundedly)."""
        while len(self._batched) >= self.config.max_compiled_batches:
            self._batched.pop(next(iter(self._batched)))
        self._batched[key] = compiled

    def _batched_compile(
        self,
        query: AnalyticsQuery,
        plan: planner_lib.Plan,
        batch: int,
        shared_table: bool,
        epochs: int,
    ) -> program_lib.CompiledProgram:
        """Compile (or fetch) the fused program for this group shape.
        All construction lives in ``program.build_program``; this method
        only re-probes the batched unroll and manages the bounded
        cache."""
        key = (
            query.cache_key_fields(), plan, batch, shared_table, epochs,
        )
        hit = self._batched.get(key)
        if hit is not None:
            return hit
        _, task, agg = self.engine._aggregate_for(query)
        if (
            plan.parallelism != "sharded"
            and plan.implementation == "xla_fold"
        ):
            # the singleton plan's unroll was probed for a single fold;
            # the vmapped executable wants its own (measured, not
            # guessed — probes.probe_batch_unroll). Kernel lanes have no
            # scan-unroll knob, so pallas_fused plans skip the re-probe.
            plan = dataclasses.replace(
                plan,
                unroll=probes.probe_batch_unroll(
                    agg, query.data, query.n_examples, plan, batch,
                    shared_table,
                ),
            )
        compiled = program_lib.build_program(
            task, agg,
            program_lib.EpochProgram(
                plan=plan, batch=batch, shared_table=shared_table,
                epochs=epochs,
            ),
            n_examples=query.n_examples,
        )
        self._batched_put(key, compiled)
        return compiled

    def _run_batch(
        self, tickets: List[Ticket], plan: planner_lib.Plan
    ) -> bool:
        """Stack the group along a new query axis and execute the whole
        multi-epoch run as ONE compiled call. Per-query RNG streams and
        ordering semantics replicate the singleton executor bit-for-bit
        (vmapped threefry splits/permutations equal the per-query ones),
        and per-lane epoch budgets freeze each lane at ITS epoch count —
        so a fused query returns the same model it would have gotten
        from ``Engine.run``. Returns False when the group fell back to
        singleton runs instead of fusing."""
        queries = [t.query for t in tickets]
        q0 = queries[0]
        b = len(queries)
        epochs = max(q.epochs for q in queries)
        budgets = jnp.asarray([q.epochs for q in queries], jnp.int32)
        ids0 = tuple(id(x) for x in jax.tree.leaves(q0.data))
        shared_table = all(
            tuple(id(x) for x in jax.tree.leaves(q.data)) == ids0
            for q in queries[1:]
        )
        if plan.parallelism == "sharded":
            if not shared_table:
                # per-query segment banks would multiply the partitioned
                # table's footprint; distinct tables stay singleton
                for t in tickets:
                    t.result = self.engine.run(t.query)
                    t.done_s = time.perf_counter()
                return False
            self._run_batch_sharded(tickets, plan, epochs, budgets)
            return True
        compiled = self._batched_compile(q0, plan, b, shared_table, epochs)
        base, keys = _vseed(jnp.asarray([q.seed for q in queries]))
        states = compiled.init_fn(base)

        def assemble():
            nonlocal keys
            if compiled.mode == "fixed" and plan.ordering == "shuffle_once":
                # ShuffleOnce consumes one split, then streams the same
                # permuted copy every epoch — one batched gather up front
                keys, subs = _vsplit(keys)
                source = (
                    q0.data if shared_table
                    else jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[q.data for q in queries],
                    )
                )
                return compiled.prep_fn(source, subs)
            if shared_table:
                # one shared table: fused runs shuffle it on device
                # in-run; clustered lanes stream it in place
                return q0.data
            return jax.tree.map(
                lambda *xs: jnp.stack(xs), *[q.data for q in queries]
            )

        def execute(examples):
            out, _ = compiled.run_fn(states, examples, keys, budgets)
            return out

        examples, states, shuffle_s, grad_s = self._timed_phases(
            assemble, execute
        )

        models = jax.vmap(compiled.agg.terminate)(states)
        if shared_table:
            loss_src = q0.data
        elif compiled.mode == "fixed" and plan.ordering == "shuffle_once":
            # examples holds the PERMUTED stack; the objective wants the
            # stored order (only branch that must stack a second time)
            loss_src = jax.tree.map(
                lambda *xs: jnp.stack(xs), *[q.data for q in queries]
            )
        else:
            loss_src = examples  # already the raw stacked tables
        losses = jax.device_get(compiled.loss_fn(models, loss_src))
        self._finish_group(
            tickets, models, losses,
            compiled.plan,  # incl. the re-probed batch unroll
            shuffle_s=shuffle_s, grad_s=grad_s,
            trace_count=compiled.trace_counter["traces"],
        )
        return True

    def _run_batch_sharded(
        self, tickets: List[Ticket], plan, epochs: int, budgets
    ) -> None:
        """Fuse same-key queries over ONE shared table into the sharded
        subsystem: the per-shard local-SGD blocks gain a leading query
        axis with per-lane epoch budgets (``runner.batched_block``), for
        EVERY ordering — B concurrent fits pay one table placement and
        one executable per block length. Init rngs and per-lane perm
        streams are the batched threefry of the singleton path, so
        per-query results equal ``Engine.run``'s."""
        from repro.engine import shard as shard_lib

        queries = [t.query for t in tickets]
        q0 = queries[0]
        b = len(queries)
        compiled = self.engine._compile(q0, plan)
        runner = compiled.epoch_fn  # program.ShardedRunner
        n = q0.n_examples

        key = ("sharded", q0.cache_key_fields(), plan, b, epochs)
        aux = self._batched.get(key)
        if aux is None:
            aux = program_lib.build_program(
                compiled.task, runner.agg,
                program_lib.EpochProgram(
                    plan=plan, batch=b, shared_table=True, epochs=epochs,
                ),
                n_examples=n,
            )
            self._batched_put(key, aux)

        def assemble():
            base, pkeys = _vseed(jnp.asarray([q.seed for q in queries]))
            mode, args, keys = shard_lib.place_batched_inputs(
                runner, q0.data, n, pkeys
            )
            return (mode, args, keys, aux.init_fn(base))

        def execute(placed):
            mode, args, keys, states = placed
            done_epochs = 0
            while done_epochs < epochs:
                block_len = min(plan.merge_period, epochs - done_epochs)
                fn = runner.batched_block(mode, block_len, n, b)
                done_arr = jnp.int32(done_epochs)
                if mode == "perm_epoch":
                    states, keys = fn(
                        states, args[0], keys, budgets, done_arr
                    )
                else:
                    states = fn(states, *args, budgets, done_arr)
                done_epochs += block_len
            return states

        _, states, shuffle_s, grad_s = self._timed_phases(assemble, execute)

        models = jax.vmap(runner.agg.terminate)(states)
        losses = jax.device_get(aux.loss_fn(models, q0.data))
        self._finish_group(
            tickets, models, losses, plan,
            shuffle_s=shuffle_s, grad_s=grad_s,
            trace_count=compiled.trace_counter["traces"],
        )

    def metrics(self) -> Dict[str, Any]:
        """The serving surface in one read: the admission/batching
        counters (including the shed and fused-lane tallies), live queue
        state, and the obs registry's ``serve.*`` aggregates — per-task
        queue-wait and end-to-end latency histograms (p50/p99) plus the
        fused assembly/execute wall breakdown."""
        return dict(
            self.stats,
            queue_depth=self.queue_depth,
            batched_plans=len(self._batched),
            slo_breaches=len(self.slo.breaches) if self.slo else 0,
            obs=obs.metrics.snapshot("serve."),
        )

    def cache_info(self) -> Dict[str, int]:
        return dict(
            self.stats,
            batched_plans=len(self._batched),
            **self.engine.cache_info(),
        )
