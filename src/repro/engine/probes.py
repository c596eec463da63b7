"""Micro-probe calibration for the cost-based planner.

The planner's constants are MEASURED, not guessed: on first contact with
a (task, table-signature) pair the engine times, on a small probe slab,
(a) a random shuffle-gather, (b) one jitted serial fold per unroll
candidate, (c) one pairwise merge, and (for kernel-eligible aggregates)
the fused-IGD Pallas lanes of the implementation axis — the same
median-of-k timing the
benchmark harness uses (``time_call`` here is the benchmarks' timing
primitive; ``benchmarks/common.py`` re-exports it). Probe cost is a few
ms once per signature; results are cached on the engine.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import obs


def time_call(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time (seconds) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


PROBE_ROWS = 256  # slab size: big enough to amortize dispatch, still ~ms
# Sharded blocks are probed on a bigger slab: device placement only pays
# off past the dispatch floor, and a 256-row slab would mis-rank it.
SHARD_PROBE_ROWS = 2048
# Segment counts the vmap'd segmented fold is probed at (mirrors the
# planner's SEGMENT_CANDIDATES; largest feasible one is measured, the
# rest are interpolated between it and the serial fold).
_SEG_PROBE_CANDIDATES = (8, 4, 2)
# Device-placement candidates per shard count: lanes-on-one-device,
# a 2-way split, and the full mesh (the probe picks by measurement).
_SHARD_LANE_UNROLL = 8


@dataclasses.dataclass(frozen=True)
class ShardPoint:
    """Measured cost of one sharded(k) decomposition on the live mesh."""

    num_shards: int
    devices: int  # probed placement: shards / devices = vmap lanes each
    epoch_seconds_per_row: float  # steady-state local-epoch cost
    block_seconds: float  # fixed per-block cost (dispatch + merge tree)
    unroll: int

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ShardPoint":
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class Calibration:
    """Per-(task, signature) measured constants (seconds)."""

    shuffle_per_row: float
    fold_per_row: Dict[int, float]  # unroll -> seconds/row
    merge_seconds: float
    probe_rows: int
    # measured vmap'd segmented-fold cost (num_segments -> seconds/row);
    # replaces the old analytic min(k, device_count) speedup model
    seg_per_row: Dict[int, float] = dataclasses.field(default_factory=dict)
    # measured sharded-block costs (num_shards -> ShardPoint); empty on a
    # single-device mesh, where the sharded plan axis does not exist
    shard: Dict[int, ShardPoint] = dataclasses.field(default_factory=dict)
    device_count: int = 1
    # the measured fused-IGD kernel lane ("pallas_fused" -> seconds/row),
    # probed on the SAME slab as the xla fold so the implementation-axis
    # ranking compares like with like; empty when the aggregate is not
    # kernel-eligible
    impl_per_row: Dict[str, float] = dataclasses.field(default_factory=dict)

    def best_unroll(self) -> int:
        return min(self.fold_per_row, key=self.fold_per_row.get)

    def seg_per_row_at(self, k: int) -> float:
        """Per-row cost of a k-segment vmap fold. The largest candidate is
        measured; other k interpolate between the serial fold (k=1) and
        the measured point on the (1 - 1/k) scan-shortening curve."""
        if k in self.seg_per_row:
            return self.seg_per_row[k]
        fold = min(self.fold_per_row.values())
        if not self.seg_per_row:
            return fold  # nothing measured: no claimed speedup
        k_ref, ref = max(self.seg_per_row.items())
        frac = (1.0 - 1.0 / k) / (1.0 - 1.0 / k_ref)
        return fold + (ref - fold) * frac

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # JSON keys are strings; from_dict restores the int keys
        d["fold_per_row"] = {str(k): v for k, v in self.fold_per_row.items()}
        d["seg_per_row"] = {str(k): v for k, v in self.seg_per_row.items()}
        # asdict already recursed into the ShardPoint dataclasses
        d["shard"] = {str(k): dict(v) for k, v in d["shard"].items()}
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Calibration":
        d = dict(d)
        d["fold_per_row"] = {int(k): v for k, v in d["fold_per_row"].items()}
        d["seg_per_row"] = {
            int(k): v for k, v in d.get("seg_per_row", {}).items()
        }
        d["shard"] = {
            int(k): ShardPoint.from_dict(p)
            for k, p in d.get("shard", {}).items()
        }
        d.setdefault("device_count", 1)
        d.setdefault("impl_per_row", {})
        return cls(**d)


_CACHE: Dict[Tuple, Calibration] = {}

# probe_runs counts actual micro-probe measurements (cache misses). The
# persistent plan cache pins this to zero across a process restart.
stats = {"probe_runs": 0}


def seed(key: Tuple, cal: Calibration) -> None:
    """Install a previously measured calibration (e.g. loaded from the
    on-disk plan cache) so ``calibrate`` never re-probes this key."""
    _CACHE[key] = cal


def calibrate(agg, data, key: Tuple, *, unrolls=(1, 8)) -> Calibration:
    """Measure the planner's constants on a probe slab of ``data``
    (stored tables hand over their head chunks — the probe measures
    time, not values, and must not materialize the table)."""
    if key in _CACHE:
        return _CACHE[key]
    stats["probe_runs"] += 1
    obs.metrics.inc("probes.runs")
    _t_calibrate = time.perf_counter()
    # opened manually (closed before the return) to avoid reindenting
    # the measurement body; an exception aborts the whole query anyway
    _span = obs.span("probe.calibrate", task=key[0] if key else "")
    _span.__enter__()

    from repro.engine import table as table_lib

    if table_lib.is_stored_table(data):
        n = data.n_rows
        rows = min(n, SHARD_PROBE_ROWS)
        slab = data.probe_slab(rows)
    else:
        n = jax.tree.leaves(data)[0].shape[0]
        # ONE slab for every per-row constant: comparing a per-row cost
        # amortized over 256 rows against one amortized over 2048
        # re-biases the exact ranking these probes exist to measure (the
        # dispatch floor inflates the small-slab number)
        rows = min(n, SHARD_PROBE_ROWS)
        slab = jax.tree.map(lambda x: x[:rows], data)
    rng = jax.random.PRNGKey(0)

    # (a) shuffle: permutation + gather, the per-epoch ShuffleAlways cost
    perm = jax.random.permutation(rng, rows)
    shuffle = jax.jit(
        lambda d, p: jax.tree.map(lambda x: jnp.take(x, p, axis=0), d)
    )
    t_shuffle = time_call(shuffle, slab, perm)

    # (b) serial fold per unroll candidate (the transition's real cost)
    from repro.core import uda as uda_lib

    state0 = agg.initialize(rng)
    fold_per_row = {}
    for u in unrolls:
        if u > rows:
            continue
        folder = jax.jit(lambda s, ex, u=u: uda_lib.fold(agg, s, ex, unroll=u))
        fold_per_row[u] = time_call(folder, state0, slab) / rows

    # (c) one pairwise merge (the segmented plan pays k-1 of these/epoch)
    merger = jax.jit(agg.merge)
    t_merge = time_call(merger, state0, state0)

    # (d) the vmap'd segmented fold at its largest feasible segment count
    # (one compile; smaller k interpolate — see seg_per_row_at). Measured,
    # not the old min(k, device_count) guess, which claimed device
    # parallelism a single-device vmap never delivers.
    seg_per_row = {}
    k_seg = next((k for k in _SEG_PROBE_CANDIDATES if rows % k == 0), None)
    if k_seg is not None:
        seg = jax.jit(
            lambda s, ex, k=k_seg: uda_lib.segmented_fold(agg, s, ex, k)
        )
        seg_per_row[k_seg] = time_call(seg, state0, slab) / rows

    # (e) the fused-IGD kernel lane (the implementation axis), on the
    # SAME slab as the xla fold: a rate amortized over a different row
    # count would re-bias the exact ranking the axis exists to measure.
    # Kernel-eligible aggregates only (catalog kernel_loss + identity
    # prox + dense (x, y) rows, or lmf's (i, j, v) ratings with tables
    # that fit the row kernel's VMEM) — everything else plans xla_fold.
    impl_per_row = _probe_implementations(agg, slab, state0, rows)

    # (f) sharded local-SGD blocks on the live device mesh (multi-device
    # only): the one probe that cannot be modeled, because placement
    # efficiency is a property of the machine (see BENCH_parallel.json:
    # on a 2-core host 2 devices beat 8; on a real pod 8 win).
    shard = {}
    device_count = jax.local_device_count()
    if device_count > 1:
        shard = _probe_sharded(agg, slab, state0, n, task_name=key[0])

    cal = Calibration(
        shuffle_per_row=t_shuffle / rows,
        fold_per_row=fold_per_row,
        merge_seconds=t_merge,
        probe_rows=rows,
        seg_per_row=seg_per_row,
        shard=shard,
        device_count=device_count,
        impl_per_row=impl_per_row,
    )
    _CACHE[key] = cal
    _span.__exit__(None, None, None)
    obs.metrics.observe(
        "probes.calibrate_s", time.perf_counter() - _t_calibrate
    )
    return cal


def _probe_implementations(agg, slab, state0, rows: int) -> Dict[str, float]:
    """Time the fused-IGD kernel lane (seconds/row) for the
    implementation axis. Empty dict when the aggregate is not
    kernel-eligible or the slab is not the kernel's rows — dense (x, y)
    rows for the dense kernel, (i, j, v) ratings for the row kernel
    (``lmf``) — the planner then never enumerates a pallas_fused
    candidate."""
    from repro.engine import program as program_lib

    loss, _why = program_lib.kernel_eligibility(agg.task, agg)
    if loss is None or not isinstance(slab, dict):
        return {}
    if loss == program_lib.ROW_KERNEL_LOSS:
        if set(slab) != {"i", "j", "v"}:
            return {}
        # the whole lane, as the epoch runs it: step sizes, the tables'
        # padding to the kernel's layout, the kernel, the unpadding
        lane = jax.jit(program_lib.kernel_lane_fold(agg, loss))
        return {"pallas_fused": time_call(lane, state0, slab) / rows}
    if (
        "x" not in slab or "y" not in slab
        or getattr(slab["x"], "ndim", 0) != 2
    ):
        return {}
    from repro.kernels.igd_fused import ops as igd_ops

    # the sequential schedule's exact per-row alphas, like the kernel
    # lane; interpret follows the backend: compiled on a TPU
    alphas = agg.step_size(state0.step + jnp.arange(rows))
    seconds = time_call(
        lambda x, y, a, w: igd_ops.igd_fold(x, y, a, w, loss=loss),
        slab["x"], slab["y"], alphas, state0.model,
    )
    return {"pallas_fused": seconds / rows}


def _min_of(fn, *args, iters: int = 5) -> float:
    """Min-of-k wall time: shard probes run on busy hosts where load only
    ever inflates a sample (the serving layer's estimator)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _probe_sharded(
    agg, probe_slab, state0, n: int, task_name: str = ""
) -> Dict[int, "ShardPoint"]:
    """Measure sharded(k) block costs for the largest feasible shard count
    over candidate device placements. Two block lengths (1 and 8 epochs)
    split the measurement into a steady-state per-epoch cost and a fixed
    per-block overhead (dispatch + merge collectives) — the two constants
    the planner's merge-period-H cost model needs. The blocks come from
    the one program compiler (``program.build_shard_block``) so the probe
    times exactly what will run.

    Non-convex tasks probe at their capped shard count (the planner only
    enumerates k <= NONCONVEX_SHARD_CAP for them; probing a k it will
    never plan would leave the reachable candidates without a measured
    point)."""
    from repro.dist import data_parallel as dp
    from repro.engine import program as program_lib
    from repro.launch import mesh as mesh_lib

    k_cap = None
    if task_name:
        try:
            from repro.engine import catalog, planner

            if catalog.get(task_name).nonconvex:
                k_cap = planner.NONCONVEX_SHARD_CAP
        except KeyError:
            pass

    devices = mesh_lib.shard_device_count()
    slab_rows = jax.tree.leaves(probe_slab)[0].shape[0]
    rows = min(n, SHARD_PROBE_ROWS, slab_rows)
    # the planner enumerates only shard counts that divide the table, so
    # a probed k must divide it too (Forest's 581,012 rows take k <= 4)
    k = next(
        (k for k in _SEG_PROBE_CANDIDATES
         if rows % k == 0 and n % k == 0 and k > 1
         and (k_cap is None or k <= k_cap)),
        None,
    )
    if k is None:
        return {}
    slab = jax.tree.map(lambda x: x[:rows], probe_slab)
    d_cands = sorted(
        {d for d in (1, 2, devices) if d <= devices and k % d == 0}
    )
    best = None
    best_t8 = float("inf")
    for d in d_cands:
        mesh = mesh_lib.shard_mesh(d)
        seg = jax.device_put(
            dp.partition_rows(slab, k), dp.shard_sharding(mesh)
        )
        timings = {}
        for block_len in (1, 8):
            blk = jax.jit(program_lib.build_shard_block(
                agg, mesh, num_shards=k, block_len=block_len,
                mode="segments", n_rows=rows, unroll=_SHARD_LANE_UNROLL,
            ))
            timings[block_len] = _min_of(blk, state0, seg, iters=9)
        # placements are ranked by the long block itself — the honest
        # end-to-end measurement; the (epoch, overhead) split below only
        # extrapolates the chosen one to other merge periods, and biases
        # the per-epoch share UP (t8/8 includes 1/8th of the overhead) so
        # the planner's claimed speedup stays conservative
        if timings[8] < best_t8:
            best_t8 = timings[8]
            epoch_s = max(timings[8] / 8.0, 1e-9)
            block_s = max(timings[1] - epoch_s, 0.0)
            best = ShardPoint(
                num_shards=k, devices=d,
                epoch_seconds_per_row=epoch_s / rows,
                block_seconds=block_s, unroll=_SHARD_LANE_UNROLL,
            )
    return {k: best} if best is not None else {}


def probe_batch_unroll(
    agg, data, n_examples: int, plan, batch: int, shared_table: bool
) -> int:
    """Measure the fused (vmapped) fold's best scan unroll on a stacked
    slab. The singleton plan's unroll was probed for a single fold; the
    batched executable has a very different overhead/compute balance
    (wider per-step ops want deeper unroll) — measured, not guessed,
    with the same methodology as ``calibrate``. Probes the exact
    variant that will run: the permuted lane for shuffle orderings, the
    plain vmapped fold for the stored order. (This lived in the serving
    front-end as its own special case; it is now part of the one probe
    layer every axis shares.)"""
    from repro.core import uda as uda_lib
    from repro.engine import program as program_lib

    if plan.scheme != "serial":
        return plan.unroll  # only the serial fold exposes the knob
    cands = sorted({plan.unroll, 8, 16})
    rows = min(n_examples, PROBE_ROWS)
    cands = [u for u in cands if u <= rows]
    if len(cands) <= 1:
        return plan.unroll
    states = jax.vmap(agg.initialize)(
        jnp.stack([jax.random.PRNGKey(i) for i in range(batch)])
    )
    permuted = plan.ordering in ("shuffle_once", "shuffle_always")
    data_axis = None if shared_table else 0
    if shared_table:
        slab = jax.tree.map(lambda x: x[:rows], data)
    else:
        slab = jax.tree.map(
            lambda x: jnp.stack([x[:rows]] * batch), data
        )
    # real (random) permutations: the run gathers rows in shuffled
    # order, and an identity gather has a different memory-access
    # cost that could mis-rank the unroll candidates
    perms = (
        jax.vmap(lambda k: jax.random.permutation(k, rows))(
            jax.random.split(jax.random.PRNGKey(0), batch)
        )
        if permuted else None
    )
    best, best_t = plan.unroll, float("inf")
    for u in cands:
        # probe the exact variant the run will use: same lane, same
        # broadcast-vs-stacked table axis
        if permuted:
            fold_u = jax.jit(jax.vmap(
                program_lib.permuted_lane(agg, u),
                in_axes=(0, data_axis, 0),
            ))
            args = (states, slab, perms)
        else:
            fold_u = jax.jit(jax.vmap(
                lambda s, ex, u=u: uda_lib.fold(agg, s, ex, unroll=u),
                in_axes=(0, data_axis),
            ))
            args = (states, slab)
        # min-of-k, not median: serving probes run on a loaded box,
        # and contention only ever inflates a sample
        t = _min_of(fold_u, *args, iters=5)
        if t < best_t:
            best, best_t = u, t
    return best


def clear_cache() -> None:
    _CACHE.clear()
