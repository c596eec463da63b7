"""Figure 9 at mesh scale: the sharded execution subsystem
(repro.engine.shard) vs the singleton executor.

Reproduces the paper's parallel speedup-vs-quality tradeoff with REAL
multi-device execution instead of the §3.3 simulator: shard counts
k ∈ {1, 2, 4, 8} x merge periods H on the glm (logreg, the fig-9
workload) and lmf (low-rank MF) tasks. Every sharded row reports wall
clock, final loss, and the delta vs the singleton run; the ``planned``
row is the acceptance check — the PLANNER must pick a sharded plan off
its mesh-probed calibration and beat the singleton wall-clock at a
final loss within 5%.

The suite needs a multi-device mesh. Run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (scripts/check.sh
does); invoked on a single-device backend it re-executes itself in a
subprocess with the forced 8-device mesh, so ``benchmarks/run.py
--json`` produces comparable ``BENCH_parallel.json`` rows either way.

On this 2-core container the probed placement is 2 devices x 4 vmap
lanes (the probe discovers that 8 host devices contending for 2 cores
lose — exactly the decision the calibration exists to measure); on a
real accelerator mesh the same plan axis spreads to the full mesh.
"""

from __future__ import annotations

import os
import subprocess
import sys

MESH_DEVICES = 8


def _rows_from_subprocess(quick: bool):
    """Re-exec this module under a forced 8-device host mesh (the flag
    must be set before the backend exists, which in-process is too
    late by the time the harness imports its first suite)."""
    if os.environ.get("REPRO_SHARD_BENCH_CHILD"):
        # forcing host devices had no effect (non-CPU backend pinned to
        # one device?) — fail here instead of recursing forever
        raise RuntimeError(
            "shard bench needs a multi-device mesh but the forced-device "
            "child still sees <2 devices; set XLA_FLAGS for a "
            "multi-device backend"
        )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["REPRO_SHARD_BENCH_CHILD"] = "1"
    # the child simulates a mesh on forced host devices; on an accelerator
    # host the parent already holds the chip, so the child stays on the CPU
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, root, env.get("PYTHONPATH")) if p
    )
    from repro.launch import mesh as mesh_lib

    mesh_lib.force_host_device_count(MESH_DEVICES, env=env)
    cmd = [sys.executable, "-m", "benchmarks.shard_bench"]
    if not quick:
        cmd.append("--full")
    out = subprocess.run(
        cmd, cwd=root, env=env, capture_output=True, text=True, timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"shard bench subprocess failed:\n{out.stderr[-3000:]}"
        )
    return [line for line in out.stdout.splitlines()
            if line.count(",") >= 2 and not line.startswith("#")]


def _best_wall(fn, trials: int = 5) -> float:
    """Min-of-k wall clock (this box's contention only inflates) — the
    probes' estimator, applied to a host-blocking call."""
    from repro.engine.probes import _min_of

    return _min_of(fn, iters=trials)


def run(quick: bool = True):
    import jax

    if jax.local_device_count() < 2:
        return _rows_from_subprocess(quick)

    from benchmarks.common import row
    from repro import engine
    from repro.data import synthetic

    rng = jax.random.PRNGKey(0)
    n = 2048 if quick else 16384
    dim = 32
    epochs = 20
    rows = []

    # ---- glm: the fig-9 workload -------------------------------------
    data = synthetic.dense_classification(rng, n, dim, clustered=False)
    q = engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": dim},
        epochs=epochs, tolerance=0.0,
    )
    eng = engine.Engine()
    report = eng.explain(q)  # mesh probes run (once) here
    point = next(iter(report.calibration.shard.values()), None)

    single_plan = engine.Plan("clustered", "serial", unroll=1)
    res_single = eng.run(q, plan=single_plan)
    wall_single = _best_wall(lambda: eng.run(q, plan=single_plan))
    loss_single = res_single.losses[-1]
    rows.append(row(
        f"fig9_shard_glm_singleton_n{n}", wall_single,
        f"loss={loss_single:.4f}",
    ))

    def sharded_row(k, h):
        d = point.devices if point is not None and k % point.devices == 0 else 1
        u = point.unroll if point is not None else 8
        plan = engine.Plan(
            "clustered", "serial", unroll=u, parallelism="sharded",
            num_shards=k, merge_period=h, shard_devices=d,
        )
        res = eng.run(q, plan=plan)
        wall = _best_wall(lambda: eng.run(q, plan=plan))
        loss = res.losses[-1]
        delta = (loss - loss_single) / abs(loss_single)
        rows.append(row(
            f"fig9_shard_glm_k{k}_H{h}_n{n}", wall,
            f"speedup={wall_single / wall:.2f}x;loss={loss:.4f};"
            f"delta={delta * 100:+.1f}%;devices={d}",
        ))

    for k in (1, 2, 4, 8):
        sharded_row(k, 1)
    for h in (5, epochs):
        sharded_row(8, h)

    # ---- the acceptance row: the planner's own choice ----------------
    res_planned = eng.run(q)
    wall_planned = _best_wall(lambda: eng.run(q))
    chosen = report.chosen
    loss_p = res_planned.losses[-1]
    delta_p = (loss_p - loss_single) / abs(loss_single)
    quality_ok = loss_p <= loss_single * 1.05  # within 5% (better is fine)
    if chosen.parallelism == "sharded":
        plan_tag = (
            f"plan=sharded(k={chosen.num_shards} H={chosen.merge_period} "
            f"d={chosen.shard_devices})"
        )
    else:
        plan_tag = "plan=NOT_SHARDED"
    rows.append(row(
        f"fig9_shard_glm_planned_n{n}", wall_planned,
        f"speedup={wall_single / wall_planned:.2f}x;"
        f"delta={delta_p * 100:+.1f}%;quality_ok={int(quality_ok)};"
        + plan_tag,
    ))

    # ---- lmf: non-convex factors through the same machinery ----------
    n_ratings = 4096 if quick else 16384
    n_rows_m, n_cols = 64, 32
    rdata = synthetic.ratings(rng, n_rows_m, n_cols, n_ratings, rank=4)
    ql = engine.AnalyticsQuery(
        task="lmf", data=rdata,
        task_args={"n_rows": n_rows_m, "n_cols": n_cols, "rank": 4,
                   "mu": 1e-3},
        epochs=10, tolerance=0.0,
    )
    engl = engine.Engine()
    res_l = engl.run(ql, plan=single_plan)
    wall_l = _best_wall(lambda: engl.run(ql, plan=single_plan), trials=3)
    loss_l = res_l.losses[-1]
    rows.append(row(
        f"fig9_shard_lmf_singleton_n{n_ratings}", wall_l,
        f"loss={loss_l:.4f}",
    ))
    # lmf is non-convex: k=8 averaging diverges and H>1 lets the factor
    # misalignment compound between merges (the reason the planner caps
    # non-convex tasks at 4 shards); the k<=4, H=1 rows measure the
    # quality penalty the paper's Fig. 9 story predicts
    for k, h in ((2, 1), (4, 1)):
        d = point.devices if point is not None and k % point.devices == 0 else 1
        plan = engine.Plan(
            "clustered", "serial", unroll=8, parallelism="sharded",
            num_shards=k, merge_period=h, shard_devices=d,
        )
        res = engl.run(ql, plan=plan)
        wall = _best_wall(lambda: engl.run(ql, plan=plan), trials=3)
        lloss = res.losses[-1]
        rows.append(row(
            f"fig9_shard_lmf_k{k}_H{h}_n{n_ratings}", wall,
            f"speedup={wall_l / wall:.2f}x;loss={lloss:.4f};"
            f"delta={(lloss - loss_l) / abs(loss_l) * 100:+.1f}%",
        ))
    return rows


if __name__ == "__main__":
    quick = "--full" not in sys.argv
    for line in run(quick=quick):
        print(line)
