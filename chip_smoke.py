#!/usr/bin/env python3
"""Smoke test of the IGD engine's main path on a TPU, at Forest scale.

The table is the paper's Forest covertype shape: 581,012 rows x 54 dense
f32 features with +-1 labels, stored clustered by label, generated on
the device from ``--seed``. Every phase goes through the entry points a
user calls (``engine.Engine``, ``launch.serve.serve_analytics``) and
prints what it found on its own lines:

* device     -- platform, device_kind, device count, JAX version;
* auto       -- ``Engine.run`` of a ``logreg`` query (3 epochs, no early
                stop) under the planner's own choice, with its EXPLAIN;
                the implementation probes must have compiled the Pallas
                kernels, and the loss must fall;
* reference  -- the serial ``xla_fold`` model against a plain
                ``jax.numpy`` reference written below (a ``lax.scan``
                over the rows with the same step sizes, in the stored
                order permuted by the plan's ordering);
* kernel     -- the same query with ``implementation=pallas_fused``;
                its model must match the ``xla_fold`` model;
* serve      -- 8 ``svm`` queries that differ only in ``seed`` through
                ``serve_analytics`` with ``max_batch=8``: one fused batch
                of 8, no errors, and a lane equal to a singleton run.

``--chips 4`` runs only the sharded phase on a four-chip host: ``logreg``
under a ``sharded(k=4)`` plan placed on 4 devices against the singleton
plan, with the planner's EXPLAIN for the 4-chip mesh. The sharded loss
may sit at most 5% above the singleton's.

Walls printed along the way are set-up information on the host clock,
not metrics. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed check exits non-zero before it. Without a TPU the script exits
non-zero and prints no result.

Usage: python chip_smoke.py [--chips 4] [--seed N]
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import engine, obs  # noqa: E402
from repro.core import tracecount, uda  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.engine import catalog, probes, program, shard, xla_cache  # noqa: E402
from repro.kernels.igd_fused import ops as igd_ops  # noqa: E402
from repro.launch.serve import make_analytics_server, serve_analytics  # noqa: E402

FOREST_ROWS, FOREST_DIM = 581_012, 54
EPOCHS = 3
SERVE_QUERIES, SERVE_EPOCHS = 8, 2
# fp32 fold tolerance between two lowerings of the same sequential fold
# after 3 epochs of 581,012 steps: the reductions add in different
# orders, so the models agree to max|w - w_ref| <= MODEL_RTOL * max|w_ref|
MODEL_RTOL = 1e-4
# sharded(k=4) local SGD is a different trajectory; like the parallel
# benchmark's quality row, its final loss may sit at most 5% above the
# singleton plan's (model averaging may also land lower)
SHARD_LOSS_RTOL = 0.05
# catalog schedule of logreg: alpha_k = 0.5 / (1 + k / n)
LOGREG_ALPHA0 = 0.5


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    for line in str(msg).splitlines():
        print(f"[{phase}] {line}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations per jitted function, so a
    phase can report its compile wall apart from its run wall, and name
    the largest compiles."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.by_fn = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.by_fn[fun_name] += duration

    def mark(self) -> collections.Counter:
        return collections.Counter(self.by_fn)

    def since(self, mark) -> str:
        new = self.by_fn - mark
        top = ", ".join(f"{k} {v:.2f}s" for k, v in new.most_common(3))
        return f"compile {sum(new.values()):.3f}s (largest: {top or 'none'})"


# ---------------------------------------------------------------------------
# the plain jax.numpy reference
# ---------------------------------------------------------------------------


@jax.jit
def reference_epoch(w, x, y, k0):
    """One epoch of sequential logistic-regression IGD over the rows in
    the order given: w <- w + a_k * y * sigmoid(-y w.x) * x with
    a_k = 0.5 / (1 + k / n), k the global step."""
    n = x.shape[0]
    alphas = LOGREG_ALPHA0 / (
        1.0 + (k0 + jnp.arange(n)).astype(jnp.float32) / n
    )

    def body(w, ex):
        xi, yi, ai = ex
        m = yi * jnp.dot(w, xi, precision=jax.lax.Precision.HIGHEST)
        return w + ai * yi * jax.nn.sigmoid(-m) * xi, None

    return jax.lax.scan(body, w, (x, y, alphas))[0]


def epoch_orders(ordering: str, seed: int, n: int, epochs: int):
    """The row order of each epoch of a serial plan: None for the stored
    order, else the permutation the query's seed defines. The ordering
    stream is fold_in(PRNGKey(seed), PERM_STREAM_SALT); a shuffle takes
    one split of it, and every epoch then takes one more."""
    rng = jax.random.fold_in(
        jax.random.PRNGKey(seed), program.PERM_STREAM_SALT
    )
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


def reference_model(data, ordering: str, seed: int, epochs: int):
    x, y = data["x"], data["y"]
    n, d = x.shape
    w = jnp.zeros((d,), jnp.float32)
    for e, perm in enumerate(epoch_orders(ordering, seed, n, epochs)):
        xe, ye = (x, y) if perm is None else (x[perm], y[perm])
        w = reference_epoch(w, xe, ye, jnp.int32(e * n))
    return w


def model_gap(w, w_ref) -> float:
    w, w_ref = np.asarray(w), np.asarray(w_ref)
    return float(np.max(np.abs(w - w_ref)) / np.max(np.abs(w_ref)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    say("device", f"platform={d0.platform} kind={d0.device_kind} "
                  f"count={len(devs)} jax={jax.__version__}")
    if d0.platform != "tpu":
        say("device", "no TPU: this smoke runs only on the chip")
        sys.exit(2)
    check(len(devs) == chips,
          f"expected {chips} chip(s), JAX sees {len(devs)} (one chip runs "
          "with no option; a four-chip host runs with --chips 4)")
    return d0, len(devs)


def logreg_query(data, seed: int, **kw):
    return engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": FOREST_DIM},
        epochs=EPOCHS, tolerance=0.0, seed=seed, **kw,
    )


def kernel_probe_compiled(n: int, d: int) -> bool:
    """The call the implementation probes make (``ops.igd_fold`` with no
    ``interpret`` argument) lowers to a Mosaic kernel, not the
    interpreter."""
    rows = min(n, probes.SHARD_PROBE_ROWS)
    f32 = jnp.float32
    text = igd_ops.igd_fold.lower(
        jax.ShapeDtypeStruct((rows, d), f32), jax.ShapeDtypeStruct((rows,), f32),
        jax.ShapeDtypeStruct((rows,), f32), jax.ShapeDtypeStruct((d,), f32),
        loss="lr",
    ).as_text()
    return "tpu_custom_call" in text


def phase_auto(eng, data, seed: int, clock: CompileClock):
    n = data["x"].shape[0]
    q = logreg_query(data, seed)
    mark, t0 = clock.mark(), time.perf_counter()
    report = eng.explain(q)
    plan_s, plan_compile = time.perf_counter() - t0, clock.since(mark)
    probe_s = obs.metrics.histogram("probes.calibrate_s").total
    say("auto", report.describe())
    rates = report.calibration.impl_per_row
    say("auto", "probed lane rates (us/row): " + ", ".join(
        f"{k}={v * 1e6:.4f}" for k, v in sorted(
            dict(rates, xla_fold=min(report.calibration.fold_per_row.values())
                 ).items())))
    check({"pallas_fused"} <= set(rates),
          "the implementation probe did not time the Pallas kernel")
    compiled = kernel_probe_compiled(n, data["x"].shape[1])
    say("auto", f"kernel probes compiled={compiled} "
                f"(interpret={igd_ops.default_interpret()})")
    check(compiled and not igd_ops.default_interpret(),
          "the implementation probes ran the Pallas interpreter")
    mark, t0 = clock.mark(), time.perf_counter()
    res = eng.run(q)
    run_s, run_compile = time.perf_counter() - t0, clock.since(mark)
    loss0 = n * math.log(2.0)  # the zero model's summed logistic loss
    say("auto", f"plan: {res.plan.axes()}")
    say("auto", f"loss {loss0:.6g} -> {res.losses[-1]:.6g} after "
                f"{res.epochs} epochs")
    say("auto", "set-up walls (host clock, not metrics):")
    say("auto", f"  planning {plan_s:.3f}s incl. probes {probe_s:.3f}s; "
                f"{plan_compile}")
    say("auto", f"  run {run_s:.3f}s incl. {run_compile}")
    check(res.epochs == EPOCHS, f"ran {res.epochs} epochs, not {EPOCHS}")
    check(np.all(np.isfinite(np.asarray(res.model))), "non-finite model")
    check(res.losses[-1] < loss0, "the loss did not fall")
    return q, res


def phase_lanes(eng, q, auto, seed: int, clock: CompileClock):
    """Both serial lowerings of the auto plan's ordering, each forced by
    a hint, against the reference and against each other; a serial auto
    plan is checked against the reference too."""
    plan = auto.plan
    serial = plan.scheme == "serial" and plan.parallelism == "singleton"
    hints = {"ordering": plan.ordering} if serial else {}

    def lane(phase, impl):
        mark, t0 = clock.mark(), time.perf_counter()
        res = eng.run(dataclasses.replace(
            q, hints=dict(hints, scheme="serial", implementation=impl)
        ))
        say(phase, f"plan: {res.plan.axes()}")
        say(phase, f"loss {res.losses[-1]:.6g}; run "
                   f"{time.perf_counter() - t0:.3f}s (host clock) incl. "
                   f"{clock.since(mark)}")
        check(res.plan.implementation == impl, f"the lane did not run {impl}")
        return res

    xla = lane("reference", "xla_fold")
    hints["ordering"] = ordering = xla.plan.ordering
    t0 = time.perf_counter()
    w_ref = reference_model(q.data, ordering, seed, EPOCHS)
    jax.block_until_ready(w_ref)
    say("reference", f"lax.scan reference, ordering={ordering}: "
                     f"{time.perf_counter() - t0:.3f}s (host clock)")
    gaps = {"xla_fold": model_gap(xla.model, w_ref)}
    if serial:
        gaps[f"auto ({plan.implementation})"] = model_gap(auto.model, w_ref)
    for name, gap in gaps.items():
        say("reference", f"{name} vs reference: max|dw|/max|w| = {gap:.3e} "
                         f"(tolerance {MODEL_RTOL:g})")
        check(gap <= MODEL_RTOL, f"{name} model differs from the reference")

    kern = lane("kernel", "pallas_fused")
    gap_k = model_gap(kern.model, xla.model)
    gap_r = model_gap(kern.model, w_ref)
    say("kernel", f"pallas_fused vs xla_fold: {gap_k:.3e}; vs reference: "
                  f"{gap_r:.3e} (tolerance {MODEL_RTOL:g})")
    check(gap_k <= MODEL_RTOL, "pallas_fused model differs from xla_fold")
    check(gap_r <= MODEL_RTOL, "pallas_fused model differs from the reference")


def phase_serve(data, seed: int, clock: CompileClock):
    queries = [
        engine.AnalyticsQuery(
            task="svm", data=data, task_args={"dim": FOREST_DIM},
            epochs=SERVE_EPOCHS, tolerance=0.0, seed=seed + i,
        )
        for i in range(SERVE_QUERIES)
    ]
    srv = make_analytics_server(max_batch=SERVE_QUERIES)
    mark, t0 = clock.mark(), time.perf_counter()
    tickets = serve_analytics(queries, server=srv)
    wall, compile_s = time.perf_counter() - t0, clock.since(mark)
    for i, t in enumerate(tickets):
        check(t.accepted, f"ticket {i} rejected: {t.reject_reason}")
        check(t.error is None, f"ticket {i} failed: {t.error}")
        check(t.result.batch_size == SERVE_QUERIES,
              f"ticket {i} ran in a batch of {t.result.batch_size}")
    plan = tickets[0].result.plan
    say("serve", f"{len(tickets)} tickets, batch sizes "
                 f"{[t.result.batch_size for t in tickets]}, no errors")
    say("serve", f"plan: {plan.axes(batch=str(SERVE_QUERIES))}")
    say("serve", f"losses: {[round(t.result.losses[-1], 3) for t in tickets]}")
    say("serve", f"stats: batches={srv.stats['batches']} "
                 f"fused_lanes={srv.stats['fused_lanes']} "
                 f"failed={srv.stats['failed_queries']}")
    say("serve", f"set-up walls (host clock, not metrics): planning, probes "
                 f"and the fused run {wall:.3f}s incl. {compile_s}")
    lane = SERVE_QUERIES // 2
    single = srv.engine.run(queries[lane])
    gap = model_gap(tickets[lane].result.model, single.model)
    say("serve", f"lane {lane} vs singleton Engine.run: {gap:.3e} "
                 f"(tolerance {MODEL_RTOL:g})")
    check(gap <= MODEL_RTOL, "a fused lane differs from its singleton run")


def phase_sharded(eng, data, seed: int, chips: int, clock: CompileClock):
    n = data["x"].shape[0]
    q = logreg_query(data, seed)
    say("sharded", eng.explain(q).describe())
    mark, t0 = clock.mark(), time.perf_counter()
    # shuffle_once: each device folds its own slice of one permutation,
    # and those index segments ride sharded over the mesh
    sh = eng.run(dataclasses.replace(q, hints={
        "parallelism": "sharded", "num_shards": chips,
        "shard_devices": chips, "ordering": "shuffle_once",
    }))
    sh_s, sh_compile = time.perf_counter() - t0, clock.since(mark)
    plan = sh.plan
    check(plan.parallelism == "sharded" and plan.num_shards == chips
          and plan.shard_devices == chips,
          f"expected sharded(k={chips}) over {chips} devices, got "
          f"{plan.axes()}")
    # the epoch stream's placement, laid out exactly as the run lays it
    spec = catalog.get("logreg")
    task = spec.make_task(dim=FOREST_DIM)
    agg = uda.IGDAggregate(task, spec.step_size(n), prox=spec.prox(task))
    runner = program.ShardedRunner(task, agg, plan, tracecount.fresh_counter())
    mode, args, _, _ = shard.place_inputs(
        runner, data, n, program.seed_streams(seed)[1]
    )
    segments = [a for a in jax.tree.leaves(args)
                if not a.sharding.is_fully_replicated]
    check(bool(segments), f"no argument of the {mode} block is sharded")
    for a in segments:
        devices = a.sharding.device_set
        say("sharded", f"{mode} segments {a.shape} on {len(devices)} devices: "
                       f"{sorted(s.data.shape for s in a.addressable_shards)}")
        check(len(devices) == chips,
              f"segments sit on {len(devices)} devices, not {chips}")
    mark, t0 = clock.mark(), time.perf_counter()
    single = eng.run(dataclasses.replace(q, hints={
        "parallelism": "singleton", "scheme": "serial",
        "ordering": plan.ordering,
    }))
    single_s, single_compile = time.perf_counter() - t0, clock.since(mark)
    rel = (sh.losses[-1] - single.losses[-1]) / abs(single.losses[-1])
    say("sharded", f"plan: {plan.axes()}")
    say("sharded", f"singleton plan: {single.plan.axes()}")
    say("sharded", f"loss sharded={sh.losses[-1]:.6g} "
                   f"singleton={single.losses[-1]:.6g} "
                   f"(sharded - singleton) / singleton = {rel:+.4f} "
                   f"(at most +{SHARD_LOSS_RTOL})")
    say("sharded", "set-up walls (host clock, not metrics):")
    say("sharded", f"  sharded run {sh_s:.3f}s incl. {sh_compile}")
    say("sharded", f"  singleton run {single_s:.3f}s incl. {single_compile}")
    check(rel <= SHARD_LOSS_RTOL,
          "sharded loss is more than 5% above the singleton's")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the sharded phase on 4 chips")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    try:
        dev, count = phase_device(args.chips)
        clock = CompileClock()
        t0 = time.perf_counter()
        data = synthetic.dense_classification(
            jax.random.PRNGKey(args.seed), FOREST_ROWS, FOREST_DIM,
            clustered=True,
        )
        jax.block_until_ready(data)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(data))
        say("data", f"Forest shape {data['x'].shape} + labels, "
                    f"{nbytes / 1e6:.1f} MB on {data['x'].devices()}, "
                    f"generated in {time.perf_counter() - t0:.3f}s")
        eng = engine.Engine()
        cache = xla_cache.status()
        say("cache", f"compilation cache {cache}")
        check(cache["error"] is None,
              f"compilation cache failed: {cache['error']}")
        if args.chips > 1:
            phase_sharded(eng, data, args.seed, args.chips, clock)
        else:
            q, auto = phase_auto(eng, data, args.seed, clock)
            phase_lanes(eng, q, auto, args.seed, clock)
            phase_serve(data, args.seed, clock)
    except SmokeFailure as e:
        print(f"[fail] {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
