"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production mesh, with NO device allocation (ShapeDtypeStruct
inputs), and record memory / cost / collective analysis for the roofline.

The production meshes need 512 host devices; ``main()`` requests them
through ``repro.launch.mesh.force_host_device_count`` (env-respecting —
an operator's own ``XLA_FLAGS`` survives) instead of the old import-time
``os.environ`` clobber. Callers importing ``run_cell`` directly (the
results/ sweep scripts) own that call themselves, before first jax use.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k --mesh single            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.jsonl                # the full 40-cell table
"""

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_archs, get_arch, shape_applicable
from repro.core import igd as igd_lib
from repro.dist import sharding as shd
from repro.launch import hlo_analysis as hlo
from repro.launch import mesh as mesh_lib
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.train import make_train_step
from repro.models import lm
from repro.optim import IGD, AdamW

# devices needed by the largest mesh this module builds (2 x 16 x 16)
DRYRUN_DEVICES = 512


def make_prefill_step(cfg):
    def prefill_step(params, batch):
        return lm.prefill(
            params, batch["tokens"], cfg, prefix_embeds=batch.get("prefix_embeds")
        )

    return prefill_step


def make_decode_step(cfg):
    def decode_step(params, batch):
        logits, cache = lm.decode_step(params, batch["tokens"], batch["cache"], cfg)
        # greedy next token (sampling lives host-side in the server loop)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return decode_step


def build_cell(cfg, shape, mesh, *, grad_accum=8, optimizer="sgd",
               compress_grads=False, seq_shard=False, igd_microsteps=False,
               cast_bf16=False):
    """Returns (jitted_fn, abstract_args) for one cell."""
    shd.set_activation_ctx(mesh, seq_shard=seq_shard)
    params_abs = jax.eval_shape(
        functools.partial(lm.init_lm, cfg), jax.random.PRNGKey(0)
    )
    pspecs = shd.param_specs(params_abs, cfg, mesh)
    params_in = shd.abstract_with_sharding(params_abs, pspecs, mesh)
    pshard = shd.shardings(pspecs, mesh)

    if shape.kind == "train":
        opt = (
            IGD(igd_lib.constant(1e-2))
            if optimizer == "sgd"
            else AdamW()
        )
        opt_abs = jax.eval_shape(opt.init, params_abs)
        ospecs = jax.tree.map(lambda _: None, opt_abs)
        # optimizer state shards like its param
        if opt_abs:
            ospecs = tuple(pspecs for _ in opt_abs)
        opt_in = (
            tuple(shd.abstract_with_sharding(o, pspecs, mesh) for o in opt_abs)
            if opt_abs
            else ()
        )
        oshard = tuple(pshard for _ in opt_abs) if opt_abs else ()

        ga = min(grad_accum, shape.global_batch)
        step_fn = make_train_step(
            cfg, opt, ga, compress_grads=compress_grads,
            igd_microsteps=igd_microsteps, cast_bf16=cast_bf16,
            param_shardings=pshard if cast_bf16 else None,
        )
        batch_abs = input_specs(cfg, shape)
        bspecs = shd.batch_specs(cfg, shape.kind, mesh, shape.global_batch)
        batch_in = shd.abstract_with_sharding(batch_abs, bspecs, mesh)
        step_idx = jax.ShapeDtypeStruct((), jnp.int32,
                                        sharding=NamedSharding(mesh, P()))
        fn = jax.jit(
            step_fn,
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        return fn, (params_in, opt_in, batch_in, step_idx)

    if shape.kind == "prefill":
        step_fn = make_prefill_step(cfg)
        batch_abs = input_specs(cfg, shape)
        bspecs = shd.batch_specs(cfg, shape.kind, mesh, shape.global_batch)
        batch_in = shd.abstract_with_sharding(batch_abs, bspecs, mesh)
        fn = jax.jit(step_fn)
        return fn, (params_in, batch_in)

    # decode
    step_fn = make_decode_step(cfg)
    batch_abs = input_specs(cfg, shape)
    cspecs = shd.cache_specs(cfg, mesh, shape.global_batch, batch_abs["cache"])
    bspecs = {
        "tokens": shd.batch_specs(cfg, shape.kind, mesh, shape.global_batch)[
            "tokens"
        ],
        "cache": cspecs,
    }
    batch_in = shd.abstract_with_sharding(batch_abs, bspecs, mesh)
    cshard = shd.shardings(cspecs, mesh)
    fn = jax.jit(step_fn, out_shardings=(None, cshard), donate_argnums=(1,))
    return fn, (params_in, batch_in)


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, grad_accum=8,
             optimizer="sgd", compress_grads=False, collect_hlo=True,
             seq_shard=False, igd_microsteps=False, cast_bf16=False,
             cfg_overrides=None, tag=None):
    cfg = get_arch(arch)
    if cfg_overrides:
        cfg = cfg.scaled(**cfg_overrides)
    shape = SHAPES[shape_name]
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
    }
    if tag:
        rec["tag"] = tag
    if not shape_applicable(cfg, shape):
        rec["status"] = "SKIP"
        rec["reason"] = "long_500k scoped to sub-quadratic families"
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    with mesh:
        fn, args = build_cell(
            cfg, shape, mesh, grad_accum=grad_accum, optimizer=optimizer,
            compress_grads=compress_grads, seq_shard=seq_shard,
            igd_microsteps=igd_microsteps, cast_bf16=cast_bf16,
        )
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax wraps in a list
            cost = cost[0] if cost else {}
        rec.update(
            status="OK",
            lower_s=round(t1 - t0, 1),
            compile_s=round(t2 - t1, 1),
            n_chips=n_chips,
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            flops=cost.get("flops"),
            bytes_accessed=cost.get("bytes accessed"),
        )
        if collect_hlo:
            text = compiled.as_text()
            stats = hlo.analyze(text)
            rec["hlo_flops"] = stats.flops
            rec["hlo_hbm_bytes"] = stats.hbm_bytes
            rec["hlo_hbm_bytes_proj"] = stats.hbm_bytes_proj
            rec["hlo_hbm_upper_bytes"] = stats.hbm_upper_bytes
            rec["collective_operand_bytes"] = stats.collective_operand_bytes
            rec["collective_traffic_bytes"] = stats.collective_traffic_bytes
            rec["collective_traffic_bytes_proj"] = (
                stats.collective_traffic_bytes_proj
            )
            rec["collectives_by_kind"] = stats.collectives_by_kind
            rec["dot_count"] = stats.dot_count
            rec["hlo_chars"] = len(text)

        params_abs = jax.eval_shape(
            functools.partial(lm.init_lm, cfg), jax.random.PRNGKey(0)
        )
        total, active = hlo.count_params(params_abs, cfg)
        rec["n_params"] = total
        rec["n_params_active"] = int(active)
        rec["model_flops"] = hlo.model_flops(cfg, shape, total, int(active))
    return rec


def run_localsgd_cell(arch: str, *, grad_accum=8, merge_period=16,
                      seq_shard=True, tag=None):
    """Multi-pod local-SGD dry-run (the paper's pure-UDA merge at pod
    granularity): per-pod model instances (leading dim sharded over "pod")
    train independently; every ``merge_period`` steps the instances are
    averaged. Cross-pod traffic only flows at merges."""
    from repro.launch.train import make_localsgd_step

    cfg = get_arch(arch)
    shape = SHAPES["train_4k"]
    mesh = make_production_mesh(multi_pod=True)
    n_pods = mesh.shape["pod"]
    rec = {"arch": arch, "shape": "train_4k", "mesh": "2x16x16",
           "kind": "train", "tag": tag or f"localsgd-H{merge_period}"}
    t0 = time.time()
    with mesh:
        shd.set_activation_ctx(mesh, seq_shard=seq_shard)
        params_abs = jax.eval_shape(
            functools.partial(lm.init_lm, cfg), jax.random.PRNGKey(0)
        )
        # per-pod specs: FSDP over "data" only, leading bank dim over "pod"
        inner_mesh = jax.make_mesh(
            (16, 16), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
        inner_specs = shd.param_specs(params_abs, cfg, inner_mesh)
        bank_specs = jax.tree.map(
            lambda s: P(*(("pod",) + tuple(s))), inner_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        bank_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((n_pods,) + a.shape, a.dtype),
            params_abs,
        )
        bank_in = shd.abstract_with_sharding(bank_abs, bank_specs, mesh)
        bank_shard = shd.shardings(bank_specs, mesh)

        opt = IGD(igd_lib.constant(1e-2))
        step_fn = make_localsgd_step(cfg, opt, grad_accum, merge_period)
        b_per_pod = shape.global_batch // n_pods
        batch_bank = {
            "tokens": jax.ShapeDtypeStruct(
                (n_pods, b_per_pod, shape.seq_len), jnp.int32,
                sharding=NamedSharding(mesh, P("pod", "data", None)),
            )
        }
        step_idx = jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P())
        )
        fn = jax.jit(step_fn, out_shardings=(bank_shard, (), None),
                     donate_argnums=(0, 1))
        lowered = fn.lower(bank_in, (), batch_bank, step_idx)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        stats = hlo.analyze(compiled.as_text())
        rec.update(
            status="OK",
            compile_s=round(time.time() - t0, 1),
            n_chips=mesh.devices.size,
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            hlo_flops=stats.flops,
            hlo_hbm_bytes_proj=stats.hbm_bytes_proj,
            collective_traffic_bytes=stats.collective_traffic_bytes,
            collective_traffic_bytes_proj=stats.collective_traffic_bytes_proj,
            collectives_by_kind=stats.collectives_by_kind,
        )
    return rec


def main():
    mesh_lib.force_host_device_count(DRYRUN_DEVICES)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--grad-accum", type=int, default=8)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--igd-microsteps", action="store_true")
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.all:
        cells = [
            (a, s)
            for a in sorted(all_archs())
            for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k")
        ]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_f = open(args.out, "a") if args.out else None
    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(
                    arch, shape, mp,
                    grad_accum=args.grad_accum,
                    optimizer=args.optimizer,
                    compress_grads=args.compress_grads,
                    collect_hlo=not args.no_hlo,
                    seq_shard=args.seq_shard,
                    igd_microsteps=args.igd_microsteps,
                )
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {
                    "arch": arch, "shape": shape,
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
                n_fail += 1
            line = json.dumps(rec)
            print(line[:400])
            if out_f:
                out_f.write(line + "\n")
                out_f.flush()
    if out_f:
        out_f.close()
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
