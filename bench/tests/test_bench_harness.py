"""The harness's entry point, and its runs driven on the CPU at a small
size: sound runs come out correct, and runs with the timed path broken
underneath come out not correct."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

import harness
from conftest import SMALL
from repro.core import uda
from repro.engine import executor, program

RUN = os.path.join(harness.BENCH, "run.py")
CELLS = ["forest_logreg_fit", "forest_select_open"]
# the serving cell warms one batch of every size up to max_batch; two
# keep the CPU run short, and arrivals far faster than the CPU serves
# them make nearly every call a fused pair. Every answer is checked, so
# the lanes a fault leaves out are among them.
TRAFFIC = {"forest_select_open": {"max_batch": 2, "rate_per_s": 400.0,
                                  "check_answers": 1000}}
# the serial singleton scheme the reference covers: on a loaded CPU, or
# with a fault that makes the probes read nothing, the planner may pick
# a segmented one
SERIAL = {"scheme": "serial", "parallelism": "singleton"}


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _run_cli(args, cwd):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=_cpu_env(),
        capture_output=True, text=True, timeout=300,
    )


def test_run_without_a_chip_fails_and_prints_no_result():
    out = _run_cli([RUN, "--workload", "forest_logreg_fit", "--seed",
                    str(2**33 + 5), "--seconds", "1", "--trace", "0"],
                   harness.ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no accelerator" in out.stderr


def test_run_from_the_benchmark_files_alone_fails(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(["bench/run.py", "--workload", "forest_logreg_fit",
                    "--seed", "1", "--seconds", "1", "--trace", "0"],
                   tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout


def _small_cell(name):
    spec = harness.load_spec()
    config = {w["name"]: w["config"] for w in spec["workloads"]}[name]
    cell = harness.resolve(name, spec, config_override=SMALL[config])
    cell.traffic.update(TRAFFIC.get(name, {}))
    return cell


def _run_small(name, seed=2**33 + 17, seconds=0.5):
    query = harness.Driver.query

    def serial_query(self, s):
        return dataclasses.replace(query(self, s), hints=SERIAL)

    with mock.patch.object(harness.Driver, "query", serial_query):
        return harness.run(_small_cell(name), seed, seconds, False,
                           started=time.perf_counter(), require_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    result, reasons = _run_small(name)
    assert reasons == [] and result["correct"]
    assert result["attempted"] >= 1
    if name != "forest_select_open":  # the overloaded CPU server sheds
        assert result["failed"] == 0
    assert list(result)[-1] == "checks"
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]
    cell = _small_cell(name)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def _half(tree):
    return jax.tree.map(lambda x: x[: x.shape[0] // 2], tree)


def _state_unchanged(mp):
    mp.setattr(program, "build_epoch_fn",
               lambda task, agg, plan: lambda s, ex, rng: s)
    mp.setattr(program, "permuted_lane",
               lambda agg, unroll: lambda s, data, perm: s)
    mp.setattr(program, "kernel_permuted_lane",
               lambda agg, loss, **kw: lambda s, data, perm: s)


def _half_the_rows(mp):
    epoch, lane, klane = (program.build_epoch_fn, program.permuted_lane,
                          program.kernel_permuted_lane)

    def build_epoch_fn(task, agg, plan):
        f = epoch(task, agg, plan)
        return lambda s, ex, rng: f(s, _half(ex), rng)

    def halve(make):
        def wrapped(*a, **kw):
            f = make(*a, **kw)
            return lambda s, data, perm: f(s, data, _half(perm))
        return wrapped

    mp.setattr(program, "build_epoch_fn", build_epoch_fn)
    mp.setattr(program, "permuted_lane", halve(lane))
    mp.setattr(program, "kernel_permuted_lane", halve(klane))


def _half_the_lanes(mp):
    """A fused call serves the first half of its lanes and leaves the
    rest at their initial state."""
    select = program._lane_select

    def lane_select(keep, new, old, axis):
        lanes = jnp.arange(keep.shape[0])
        return select(keep & (lanes < keep.shape[0] // 2), new, old, axis)

    mp.setattr(program, "_lane_select", lane_select)


def _early_stop(mp):
    """The program stops early: a fit after 2 of its 5 epochs (and says
    so), a serving query before its one epoch, alone (and says so) or in
    a fused call (which says it ran)."""
    run, select = executor.Engine.run, program._lane_select
    mp.setattr(executor.Engine, "run", lambda self, q, **kw: run(
        self, dataclasses.replace(q, epochs=q.epochs // 2), **kw))
    mp.setattr(program, "_lane_select", lambda keep, new, old, axis: select(
        keep & False, new, old, axis))


def _answer_altered(mp):
    mp.setattr(uda.IGDAggregate, "terminate", lambda self, state: jax.tree.map(
        lambda x: x * jnp.asarray(1.01, x.dtype), state.model))


# the faults each cell can have: a fit's batch is the table's rows, a
# fused serving call's batch is its lanes (one chip: no exchange)
FAULTS = {
    "forest_logreg_fit": [_state_unchanged, _half_the_rows, _early_stop,
                          _answer_altered],
    "forest_select_open": [_state_unchanged, _half_the_rows, _half_the_lanes,
                           _early_stop, _answer_altered],
}


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS for fault in FAULTS[name]
], ids=lambda v: getattr(v, "__name__", v))
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    result, reasons = _run_small(name, seed=2**32 + 3)
    assert not result["correct"] and reasons
    over = [k for k, c in result["checks"].items() if c["value"] > c["limit"]]
    assert over, result["checks"]
    if fault is _early_stop and name == "forest_logreg_fit":
        assert "off_epochs" in over
    json.dumps(result)
