"""Kernel smoke for scripts/check.sh: force the implementation axis to
the fused-IGD Pallas lane end-to-end (plan -> EXPLAIN -> run) and hold
the result against the jnp reference oracle, plus the EXPLAIN goldens:
the composed-axes line names the implementation axis, the why line
carries the probe-measured us/epoch per implementation, and the kernel
wall shows up in the metrics registry."""

import jax
import numpy as np

from repro import engine, obs
from repro.data import synthetic
from repro.kernels.igd_fused import ref as igd_ref

data = synthetic.dense_classification(jax.random.PRNGKey(0), 512, 8)


def q(**hints):
    return engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 8}, seed=0,
        epochs=3, tolerance=0.0, hints=hints,
    )


eng = engine.Engine()

# -- EXPLAIN goldens: five axes, probe-priced why line ----------------------
rep = eng.explain(q(implementation="pallas_fused", ordering="clustered"))
assert "implementation=pallas_fused" in rep.chosen.axes(), rep.chosen.axes()
text = eng.explain(q()).describe()
assert "impl-probed" in text and "us/epoch" in text, text
assert "implementation=xla_fold" in eng.explain(q()).axes

# -- forced kernel run vs the jnp oracle ------------------------------------
res = eng.run(q(implementation="pallas_fused", ordering="clustered"))
assert res.plan.implementation == "pallas_fused"

spec = engine.catalog.get("logreg")
task = spec.make_task(dim=8)
alphas = spec.step_size(512)(np.arange(3 * 512))
w = np.zeros(8, np.float32)
for e in range(3):
    w = np.asarray(igd_ref.igd_fold_ref(
        data["x"], data["y"], jax.numpy.asarray(alphas[e * 512:(e + 1) * 512]),
        jax.numpy.asarray(w), loss="lr",
    ))
np.testing.assert_allclose(np.asarray(res.model), w, rtol=1e-5, atol=1e-6)

# -- the kernel wall is instrumented ----------------------------------------
snap = obs.metrics.snapshot()
assert snap["engine.epoch.grad_s"]["count"] > 0, sorted(snap)

# -- xla_fold forced == default, bit for bit --------------------------------
ref = eng.run(q(ordering="clustered", scheme="serial"))
forced = eng.run(q(ordering="clustered", scheme="serial",
                   implementation="xla_fold"))
assert np.array_equal(np.asarray(forced.model), np.asarray(ref.model))

print("kernel smoke OK: pallas_fused end-to-end matches the jnp oracle; "
      "EXPLAIN surfaces the implementation axis")
