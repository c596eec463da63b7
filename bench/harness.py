"""One run of one benchmark cell, as ``run.py`` drives it.

Everything that belongs to a cell is found by name:

* the cell, in ``BENCHMARK.json``'s ``workloads``;
* its configuration, the JSON file that entry names, with the module of
  the same name beside it (generator, reference, operation and byte
  counts);
* its traffic mix, ``traffic/<traffic>.json``, read by the one driver
  its ``loop`` names (``closed``: fits back to back; ``open``: a fixed
  set of Poisson arrivals to the serving front end);
* its limits, ``limits/<cell>.json``;
* each per-layer metric's reader, ``metrics/<name>.py``, or for a name
  with a suffix (``fold_roofline.fit``) ``metrics/<stem>.py``.

A run generates the table on the device from the seed, builds the
program's own ``Engine`` (or serving front end) with no plan store, warms
up every program the traffic will use, measures for ``seconds``, and then
compares a seeded sample of the answers due in the window with the
configuration's plain reference.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import itertools
import json
import math
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402

import reference as ref_lib  # noqa: E402


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


# loaded by path: the name ``trace`` is also a module of the standard library
trace_lib = _load_module(os.path.join(BENCH, "trace.py"), "bench_trace")

SEED_MAX = 2**31 - 1  # the program's query seeds are int32


class NoChip(RuntimeError):
    """The machine has no accelerator, or fewer chips than the cell asks."""


# ---------------------------------------------------------------------------
# resolving a cell from BENCHMARK.json by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    module: Any
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, spec: Optional[dict] = None, root: str = ROOT,
            config_override: Optional[dict] = None) -> Cell:
    """The cell's files, found by the names in ``BENCHMARK.json``.
    ``config_override`` replaces top-level keys of the configuration
    (the tests run the harness at a size the CPU holds)."""
    spec = spec if spec is not None else load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    c = configs[w["config"]]
    cfg_path = os.path.join(root, c["file"])
    config = dict(_read_json(cfg_path), **(config_override or {}))
    module = _load_module(cfg_path[: -len(".json")] + ".py",
                          f"bench_config_{c['name']}")
    bench = os.path.join(root, "bench")
    traffic = _read_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    limits = _read_json(os.path.join(bench, "limits", w["name"] + ".json"))
    return Cell(
        name=w["name"], chips=int(w["chips"]), config=config, module=module,
        traffic=traffic, limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, workload)],
    )


def reader_path(metric: str, root: str = ROOT) -> str:
    """``metrics/<name>.py``, else ``metrics/<stem>.py`` for ``stem.suffix``."""
    base = os.path.join(root, "bench", "metrics")
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(base, stem + ".py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def load_reader(metric: str, root: str = ROOT) -> Callable:
    path = reader_path(metric, root)
    name = "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    return _load_module(path, name).read


def peaks_for(device_kind: str) -> dict:
    table = _read_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(have {sorted(table['devices'])})"
        )
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# seeds, compiles, spans
# ---------------------------------------------------------------------------


class Seeds:
    """Streams drawn from the run's ``--seed`` (any size): the table's
    key, the warm-up queries, the window's queries, the arrival order and
    the sample of answers checked each have a stream of their own."""

    def __init__(self, seed: int):
        self.seed = int(seed)

    def stream(self, purpose: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, purpose])

    def distinct(self, purpose: int):
        """Distinct query seeds, drawn as they are needed."""
        rng, seen = self.stream(purpose), set()
        while True:
            v = int(rng.integers(0, SEED_MAX))
            if v not in seen:
                seen.add(v)
                yield v

    def ints(self, purpose: int, count: int) -> List[int]:
        return list(itertools.islice(self.distinct(purpose), count))

    def table_key(self):
        return jax.random.PRNGKey(self.ints(1, 1)[0])


class CompileClock:
    """Counts JAX's backend compiles (a persistent-cache hit is not one)
    and sums their durations per jitted function."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.by_fn = collections.Counter()
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.by_fn[fun_name] += duration
            self.count += 1

    def top(self, n: int = 3) -> str:
        return ", ".join(f"{k} {v:.2f}s" for k, v in self.by_fn.most_common(n))


class GcClock:
    """Counts the collector's runs and their pauses while it is armed."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)
            self._t0 = None

    def close(self) -> str:
        gc.callbacks.remove(self._on)
        worst = max(self.pauses, default=0.0)
        return (f"{len(self.pauses)} collections, {sum(self.pauses):.4f}s "
                f"in all, longest {worst:.4f}s")


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _hist(name: str):
    from repro import obs

    h = obs.metrics.histogram(name)
    return h.count, h.total


# ---------------------------------------------------------------------------
# the two drivers a traffic mix can name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Answer:
    seed: int
    model: Any
    loss: float
    ordering: str
    epochs: int
    plan: str
    covered: bool  # a serial singleton plan: the reference's semantics


def _answer(seed: int, res) -> Answer:
    plan = res.plan
    return Answer(
        seed=seed, model=res.model,
        loss=float(res.losses[-1]) if res.losses else math.nan,
        ordering=plan.ordering, epochs=res.epochs, plan=plan.axes(),
        covered=plan.scheme == "serial" and plan.parallelism == "singleton",
    )


class Driver:
    def __init__(self, cell: Cell, data, seeds: Seeds):
        self.cell, self.data, self.seeds = cell, data, seeds
        t = cell.traffic
        self.task = t["task"]
        self.task_args = cell.module.task_args(cell.config, self.task)
        # the lanes of each device call in the window
        self.calls: List[int] = []
        self.answers: List[Answer] = []
        self.attempted = 0
        self.failed = 0
        self.errors: List[str] = []
        self.window_info: Dict[str, float] = {}

    def query(self, seed: int):
        from repro import engine

        t = self.cell.traffic
        return engine.AnalyticsQuery(
            task=self.task, data=self.data, task_args=self.task_args,
            epochs=int(t["epochs"]), tolerance=float(t["tolerance"]),
            seed=seed,
        )

    def close(self) -> None:
        pass


class ClosedLoop(Driver):
    """One analyst: the next fit starts when the last one has returned.
    The fit in flight when the window ends runs to completion."""

    def setup(self) -> None:
        from repro import engine

        self.engine = engine.Engine()
        with span("bench.warmup"):
            res = self.engine.run(self.query(self.seeds.ints(2, 1)[0]))
            jax.block_until_ready(res.model)
        say(f"plan: {res.plan.axes()}")

    def window(self, seconds: float) -> Dict[str, float]:
        seeds = self.seeds.distinct(3)
        g0 = _hist("engine.epoch.grad_s")
        t0 = time.perf_counter()
        deadline = t0 + seconds
        last = t0
        while time.perf_counter() < deadline:
            seed = next(seeds)
            self.attempted += 1
            with span("bench.fit"):
                res = self.engine.run(self.query(seed))
                jax.block_until_ready(res.model)
            last = time.perf_counter()
            self.calls.append(1)
            self.answers.append(_answer(seed, res))
        g1 = _hist("engine.epoch.grad_s")
        done = len(self.answers)
        self.window_info = {
            "start": t0, "end": last, "fits": done,
            "epochs": g1[0] - g0[0], "epoch_s": g1[1] - g0[1],
        }
        return {"fit_s": (last - t0) / done}

    def close(self) -> None:
        self.engine = None


class OpenLoop(Driver):
    """Independent tenants: arrivals due on a schedule whatever the
    server is doing. The schedule is the traffic's own: the quantiles of
    an exponential at its rate, in the order its ``arrival_seed`` draws,
    so every run offers the same arrivals at the same times, and the run's
    seed changes the table and the queries' seeds."""

    def setup(self) -> None:
        from repro.launch.serve import make_analytics_server

        t = self.cell.traffic
        self.server = make_analytics_server(max_batch=int(t["max_batch"]))
        warm = iter(self.seeds.ints(2, 64))
        with span("bench.warmup"):
            for b in range(1, int(t["max_batch"]) + 1):
                tickets = [self.server.submit(self.query(next(warm)))
                           for _ in range(b)]
                self.server.pump()
                for tk in tickets:
                    if tk.error or not tk.done:
                        raise RuntimeError(f"warm-up batch of {b} failed: "
                                           f"{tk.error or tk.reject_reason}")
                    if tk.result.batch_size != b:
                        raise RuntimeError(
                            f"warm-up batch of {b} ran as "
                            f"{tk.result.batch_size}")
        say(f"plan: {tickets[0].result.plan.axes()}")

    def schedule(self, seconds: float):
        rate = float(self.cell.traffic["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
        gaps = gaps * (seconds / gaps.sum())
        order = np.random.default_rng(int(self.cell.traffic["arrival_seed"]))
        gaps = order.permutation(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return list(zip(due.tolist(), self.seeds.ints(3, n)))

    def window(self, seconds: float) -> Dict[str, float]:
        arrivals = self.schedule(seconds)
        srv = self.server
        stats0 = dict(srv.stats)
        tickets = []
        pumps = []  # (wall, main-thread CPU, lanes) of each device call
        late = 0.0
        t0 = time.perf_counter()
        i = 0
        while i < len(arrivals) or srv.queue_depth:
            now = time.perf_counter()
            while i < len(arrivals) and t0 + arrivals[i][0] <= now:
                due, seed = arrivals[i]
                late = max(late, now - (t0 + due))
                tickets.append((t0 + due, seed, srv.submit(self.query(seed))))
                i += 1
            if srv.queue_depth:
                w0, c0 = time.perf_counter(), time.thread_time()
                with span("bench.pump"):
                    lanes = srv.pump()
                pumps.append((time.perf_counter() - w0,
                              time.thread_time() - c0, lanes))
                self.calls.append(lanes)
            elif i < len(arrivals):
                with span("bench.await_arrival"):
                    time.sleep(max(0.0, t0 + arrivals[i][0]
                                   - time.perf_counter()))
        end = t0 + seconds
        latencies, completed, in_window, last = [], 0, 0, t0
        for due, seed, tk in tickets:
            self.attempted += 1
            if not tk.accepted or tk.error is not None or not tk.done:
                self.failed += 1
                latencies.append(math.inf)
                if tk.error is not None:
                    self.errors.append(tk.error)
                continue
            latencies.append(tk.done_s - due)
            in_window += tk.done_s <= end
            completed += 1
            last = max(last, tk.done_s)
            self.answers.append(_answer(seed, tk.result))
        latencies.sort()
        p90 = latencies[max(0, math.ceil(0.9 * len(latencies)) - 1)]
        d = {k: srv.stats[k] - stats0[k] for k in stats0}
        self.window_info = {
            "start": t0, "end": time.perf_counter(),
            "queries": len(tickets), "generator_late_s": late,
            "served": d["batched_queries"] + d["singleton_queries"],
            "device_calls": d["batches"] + d["singleton_queries"],
        }
        say(f"{len(tickets)} arrivals, {in_window} completed in the "
            f"window, the last {last - end:.4f}s after it; p50 "
            f"{latencies[len(latencies) // 2]:.4f}s, generator at most "
            f"{late:.4f}s late, batch sizes {collections.Counter(self.calls)}")
        say("longest device calls (wall, main-thread CPU, lanes): "
            + ", ".join(f"({w:.4f}s, {c:.4f}s, {n})"
                        for w, c, n in sorted(pumps)[-3:]))
        return {"query_p90_s": p90,
                "queries_per_s": completed / (last - t0) if completed else 0.0}

    def close(self) -> None:
        self.server = None


DRIVERS = {"closed": ClosedLoop, "open": OpenLoop}


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------


def sample_answers(answers: List[Answer], count: int, seeds: Seeds):
    if len(answers) <= count:
        return list(answers)
    pick = seeds.stream(5).choice(len(answers), size=count, replace=False)
    return [answers[i] for i in sorted(pick)]


def compare(cell: Cell, data, answers: List[Answer]) -> Dict[str, float]:
    """The worst model gap and loss gap over the answers, against the
    configuration's reference run over the same rows in the same order,
    for the epochs the traffic asks (not those an answer reports)."""
    mod, cfg, task = cell.module, cell.config, cell.traffic["task"]
    epochs = int(cell.traffic["epochs"])
    worst = {"model_gap": 0.0, "loss_gap": 0.0}
    for a in answers:
        if not a.covered:
            raise ValueError(f"the reference does not cover plan {a.plan}")
        t0 = time.perf_counter()
        with span("bench.reference"):
            model = mod.reference_fit(cfg, data, task, a.seed, epochs,
                                      a.ordering)
            loss = mod.reference_loss(cfg, data, task, model)
        say(f"reference of seed {a.seed} ({a.ordering}, {epochs} epochs) "
            f"{time.perf_counter() - t0:.3f}s")
        worst["model_gap"] = max(worst["model_gap"],
                                 ref_lib.model_gap(a.model, model))
        worst["loss_gap"] = max(worst["loss_gap"],
                                ref_lib.loss_gap(a.loss, loss))
    return worst


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_info(chips: int, require_chip: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if require_chip and d0.platform == "cpu":
        raise NoChip(f"JAX finds no accelerator (platform {d0.platform})")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


@contextlib.contextmanager
def _profiled(enabled: bool):
    """A ``jax.profiler`` trace of the block, reduced and deleted on exit
    (the summary lands in the yielded dict)."""
    box: Dict[str, Any] = {}
    if not enabled:
        yield box
        return
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as log_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield box
        finally:
            jax.profiler.stop_trace()
        box["summary"] = trace_lib.summarize(
            trace_lib.load(trace_lib.find_trace(log_dir))
        )


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader sees."""

    cell: Cell
    driver: Driver
    setup: Dict[str, float]
    trace: Optional[trace_lib.Summary]
    peaks: dict


def fold_least_s(ctx: Context) -> float:
    """The roofline's least time for the fold work the window completed:
    for each device call, the operations of the epochs the traffic asks
    over peak FLOP/s or their bytes over peak HBM bandwidth, whichever is
    larger. Says which binds."""
    mod, cfg, task = ctx.cell.module, ctx.cell.config, ctx.cell.traffic["task"]
    epochs = int(ctx.cell.traffic["epochs"])
    total = flop_s = byte_s = 0.0
    for lanes in ctx.driver.calls:
        flops, nbytes = mod.epoch_work(cfg, task, lanes)
        f = epochs * flops / ctx.peaks["flops_per_s"]
        b = epochs * nbytes / ctx.peaks["hbm_bytes_per_s"]
        total += max(f, b)
        flop_s, byte_s = flop_s + f, byte_s + b
    say(f"fold roofline: {'HBM' if byte_s >= flop_s else 'compute'} bound "
        f"binds ({byte_s:.6f}s of HBM, {flop_s:.6f}s of compute at peak)")
    return total


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        started: float, require_chip: bool = True) -> Tuple[dict, List[str]]:
    """One run: the result object that ``run.py`` prints, and the reasons
    it is not correct (none when it is). ``started`` is the host clock at
    process start; ``require_chip=False`` lets the tests drive a run on
    the CPU."""
    stages = [("imports", time.perf_counter())]
    device = device_info(cell.chips, require_chip)
    peaks = peaks_for(device["kind"]) if require_chip else None
    stages.append(("device", time.perf_counter()))
    clock = CompileClock()
    seeds = Seeds(seed)
    with span("bench.generate"):
        data = cell.module.generate(cell.config, seeds.table_key())
        jax.block_until_ready(data)
    stages.append(("table", time.perf_counter()))
    driver = DRIVERS[cell.traffic["loop"]](cell, data, seeds)
    driver.setup()
    setup = {"probe_s": _hist("probes.calibrate_s")[1]}
    stages.append(("plan and warm-up", time.perf_counter()))
    compiles0 = clock.count
    # what set-up made lives on: later collections need not walk it
    gc.collect()
    gc.freeze()
    stages.append(("collect", time.perf_counter()))
    setup_s = time.perf_counter() - started
    gcs = GcClock()
    with _profiled(trace) as box:
        with span("bench.window"):
            measured = driver.window(seconds)
    in_window = clock.count - compiles0
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    say(f"set-up {setup_s:.3f}s: " + ", ".join(
        f"{name} {t - t_prev:.3f}s" for (name, t), t_prev in
        zip(stages, [started] + [t for _, t in stages])))
    say(f"{compiles0} compiles in set-up (largest: {clock.top() or 'none'}), "
        f"{in_window} in the window; garbage collector in the window: "
        f"{gcs.close()}")
    gc.unfreeze()
    summary = box.get("summary")

    metrics: Dict[str, dict] = {}
    if trace:
        ctx = Context(cell, driver, setup, summary, peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        measured["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}

    attempted, failed = driver.attempted, driver.failed
    reasons = list(driver.errors)
    epochs = int(cell.traffic["epochs"])
    short = [a for a in driver.answers if a.epochs != epochs]
    if short:
        reasons.append(f"{len(short)} answers ran other than the {epochs} "
                       f"epochs asked (seed {short[0].seed}: "
                       f"{short[0].epochs})")
    checks: Dict[str, dict] = {
        "off_epochs": {"value": len(short), "limit": 0}}
    answers = sample_answers(driver.answers,
                             int(cell.traffic["check_answers"]), seeds)
    driver.close()
    del driver
    gc.collect()
    if not answers:
        reasons.append("no answer completed in the window")
    try:
        worst = compare(cell, data, answers)
    except ValueError as e:
        reasons.append(str(e))
    else:
        checks.update({k: {"value": worst[k], "limit": limit}
                       for k, limit in cell.limits.items()})
        say("gaps read: " + ", ".join(f"{k} {v!r}" for k, v in worst.items()))
        reasons += [
            f"{k} {c['value']:.6g} is over its limit {c['limit']:.6g}"
            for k, c in checks.items()
            if k in cell.limits and not c["value"] <= c["limit"]
        ]
    result = {
        "correct": not reasons,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.ops,
                               "idle_gaps": summary.gaps}
    result["checks"] = checks
    return result, reasons
