"""Distributed correctness: the pjit-sharded train step must match the
single-device step bit-for-bit (up to float tolerance), and the dry-run
machinery must build/compile cells on a small mesh. Runs in a subprocess so
the 8-device XLA flag never leaks into other tests."""

import os
import subprocess
import sys

import pytest

SCRIPT_MATCH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ArchConfig
from repro.core import igd
from repro.data import synthetic
from repro.dist import sharding as shd
from repro.launch.train import make_train_step
from repro.models import lm
from repro.optim import IGD

cfg = ArchConfig("d-lm", "dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, d_ff=128, vocab=128, dtype="float32",
                 remat=False)
rng = jax.random.PRNGKey(0)
params = lm.init_lm(cfg, rng)
opt = IGD(igd.constant(0.05))
data = synthetic.token_stream(rng, 16, 32, cfg.vocab)
step = make_train_step(cfg, opt, grad_accum=2)

# single device
p1, _, m1 = jax.jit(step)(params, (), data, jnp.int32(0))

# 4x2 mesh, sharded
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
shd.set_activation_ctx(mesh)
pspecs = shd.param_specs(params, cfg, mesh)
pshard = shd.shardings(pspecs, mesh)
params_s = jax.device_put(params, pshard)
bspecs = shd.batch_specs(cfg, "train", mesh, 16)
data_s = jax.device_put(data, shd.shardings(bspecs, mesh))
with mesh:
    p2, _, m2 = jax.jit(step, out_shardings=(pshard, (), None))(
        params_s, (), data_s, jnp.int32(0))
shd.set_activation_ctx(None)

err = max(float(jnp.max(jnp.abs(a - jax.device_get(b))))
          for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
loss_err = abs(float(m1["loss"]) - float(m2["loss"]))
print(f"param_err={err:.3e} loss_err={loss_err:.3e}")
assert err < 5e-4, err
assert loss_err < 1e-4, loss_err
print("DIST_MATCH_OK")
"""

SCRIPT_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax
import repro.launch.dryrun as dr
import repro.configs.base as base

def small_mesh(*, multi_pod=False):
    t = (jax.sharding.AxisType.Auto,)
    if multi_pod:
        return jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=t*3)
    return jax.make_mesh((4, 2), ("data", "model"), axis_types=t*2)
dr.make_production_mesh = small_mesh
base.SHAPES["train_4k"] = dataclasses.replace(base.SHAPES["train_4k"], seq_len=256, global_batch=8)
base.SHAPES["decode_32k"] = dataclasses.replace(base.SHAPES["decode_32k"], seq_len=512, global_batch=8)
from repro.configs import get_arch
cfg = get_arch("llama3.2-3b").scaled(name="t", n_layers=2, d_model=128,
    n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256, vocab=512)
base._REGISTRY["t"] = cfg
for shape, mp in [("train_4k", False), ("train_4k", True), ("decode_32k", False)]:
    rec = dr.run_cell("t", shape, mp, grad_accum=2)
    assert rec["status"] == "OK", rec
    assert rec["hlo_flops"] > 0
    assert rec["collective_traffic_bytes"] > 0
print("DRYRUN_SMALL_OK")
"""


def _run(script: str, marker: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # a forced-host-device mesh, never the chip
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert marker in out.stdout, (out.stdout[-2000:], out.stderr[-3000:])


def test_sharded_train_step_matches_single_device():
    _run(SCRIPT_MATCH, "DIST_MATCH_OK")


def test_dryrun_machinery_on_small_mesh():
    _run(SCRIPT_DRYRUN, "DRYRUN_SMALL_OK")
