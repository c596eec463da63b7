"""Elastic scaling: a checkpoint written on one device layout restores,
correctly re-sharded, onto a different mesh — and training continues with
identical results. Subprocess (needs multiple host devices)."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ArchConfig
from repro.core import igd
from repro.data import synthetic
from repro.launch.elastic import elastic_restore, shardings_for
from repro.launch.train import make_train_step
from repro.ckpt import CheckpointManager
from repro.models import lm
from repro.optim import IGD
import tempfile

cfg = ArchConfig("el-lm", "dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, d_ff=128, vocab=128, dtype="float32",
                 remat=False)
rng = jax.random.PRNGKey(0)
opt = IGD(igd.constant(0.05), momentum=0.9)
params = lm.init_lm(cfg, rng)
opt_state = opt.init(params)
data = synthetic.token_stream(rng, 16, 32, cfg.vocab)
step = make_train_step(cfg, opt, grad_accum=2)

# train 3 steps on a 2x4 mesh, checkpoint
mesh_a = jax.make_mesh((2, 4), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
pshard_a, oshard_a = shardings_for(cfg, mesh_a, opt)
p = jax.device_put(params, pshard_a)
o = tuple(jax.device_put(t, pshard_a) for t in opt_state)
with mesh_a:
    for k in range(3):
        p, o, m = jax.jit(step)(p, o, data, jnp.int32(k))
ckpt = tempfile.mkdtemp()
mgr = CheckpointManager(ckpt, async_write=False)
mgr.save(3, {"params": p, "opt": o}, meta={"pipeline": {"epoch": 0, "cursor": 0, "seed": 0}})

# continue 2 more steps on mesh A (reference trajectory)
pa, oa = p, o
with mesh_a:
    for k in range(3, 5):
        pa, oa, _ = jax.jit(step)(pa, oa, data, jnp.int32(k))

# ELASTIC: restore onto a DIFFERENT mesh (4x2) and continue
mesh_b = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
pb, ob, meta = elastic_restore(ckpt, cfg, opt, mesh_b)
assert meta["step"] == 3
with mesh_b:
    for k in range(3, 5):
        pb, ob, _ = jax.jit(step)(pb, ob, data, jnp.int32(k))

err = max(float(jnp.max(jnp.abs(jax.device_get(a) - jax.device_get(b))))
          for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)))
print(f"elastic trajectory err={err:.3e}")
assert err < 5e-4, err
# scale-down: restore onto a single device
pc, oc, _ = elastic_restore(ckpt, cfg, opt, None)
err1 = max(float(jnp.max(jnp.abs(jax.device_get(a) - np.asarray(b))))
           for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(pc)))
assert err1 < 1e-6, err1
print("ELASTIC_OK")
"""


def test_elastic_restore_across_meshes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # a forced-host-device mesh, never the chip
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert "ELASTIC_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-3000:])
