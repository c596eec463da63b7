"""Distributed flash-decode (length-sharded KV cache + logsumexp combine)
matches the unsharded oracle. Subprocess (needs >1 host device)."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.dist.collectives import sharded_flash_decode
from repro.kernels.decode import ops as dops

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = jax.random.PRNGKey(0)
b, h, kv, hd, s = 2, 8, 4, 64, 1024
q = jax.random.normal(rng, (b, h, hd), jnp.float32)
kc = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, kv, hd), jnp.float32)
vc = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, kv, hd), jnp.float32)

for length in (1, 300, 640, 1024):
    ref = dops.decode_attention(q, kc, vc, length, use_kernel=False)
    with jax.set_mesh(mesh):
        out = sharded_flash_decode(q, kc, vc, jnp.int32(length), mesh)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"len={length} err={err:.2e}")
    assert err < 5e-5, (length, err)
print("SHARDED_DECODE_OK")
"""


def test_sharded_flash_decode_matches_oracle():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # a forced-host-device mesh, never the chip
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert "SHARDED_DECODE_OK" in out.stdout, (
        out.stdout[-2000:], out.stderr[-3000:]
    )
