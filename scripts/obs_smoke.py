"""Observability smoke for scripts/check.sh: run one query traced and
one untraced, validate the exported JSONL trace against the fixed span
schema, check the Chrome-trace export, EXPLAIN ANALYZE's per-axis
table (now with critical-path attribution), the serving metrics
surface, pin the disabled path to zero recorded spans — then the
operational tier: scrape /metrics and /healthz off a live obs server,
parse the exposition, force a synthetic SLO breach with a tiny queue
under burst load, and validate the incident JSONL dump."""

import json
import os
import tempfile
import urllib.request

import jax

from repro import engine, obs
from repro.data import synthetic
from repro.engine import serve
from repro.launch import obs_server
from repro.obs import export, slo, trace

data = synthetic.dense_classification(jax.random.PRNGKey(0), 512, 8)


def q(seed=0, epochs=3):
    return engine.AnalyticsQuery(
        task="logreg", data=data, task_args={"dim": 8}, seed=seed,
        epochs=epochs, tolerance=0.0,
    )


eng = engine.Engine()

# -- traced run: export + schema validation ---------------------------------
with obs.tracing() as rec:
    eng.run(q())
names = {s["name"] for s in rec.spans}
for expected in ("engine.run", "engine.compile", "engine.epoch"):
    assert expected in names, (expected, names)
with tempfile.TemporaryDirectory() as tmp:
    jsonl = os.path.join(tmp, "trace.jsonl")
    chrome = os.path.join(tmp, "trace.json")
    n = rec.export_jsonl(jsonl)
    assert trace.validate_jsonl(jsonl) == n > 0
    assert rec.export_chrome_trace(chrome) == n
    with open(chrome) as f:
        assert len(json.load(f)["traceEvents"]) == n
print(f"traced query: {n} spans, JSONL schema valid")

# -- disabled path: zero spans recorded -------------------------------------
before = len(rec)
assert not obs.enabled()
eng.run(q(seed=1))
assert len(rec) == before, "disabled tracer recorded spans"
print("disabled path: zero spans recorded")

# -- EXPLAIN ANALYZE: per-axis predicted vs measured ------------------------
rep = eng.explain_analyze(q(seed=2, epochs=4))
assert [r.axis for r in rep.rows] == [
    "ordering", "parallelism", "batching", "source", "implementation",
]
assert rep.epochs_run == 4 and rep.measured_total_s > 0
assert rep.attribution is not None, "EXPLAIN ANALYZE lost attribution"
assert rep.attribution["root"] == "engine.run"
print(rep.describe())

# -- serving metrics surface ------------------------------------------------
srv = serve.ServingEngine(serve.ServeConfig(max_batch=4), engine=eng)
tickets = [srv.submit(q(seed=s)) for s in range(3)]
srv.drain()
assert all(t.done for t in tickets)
m = srv.metrics()
assert m["accepted"] == 3 and m["shed_queue_full"] == 0
assert m["obs"]["serve.accepted"]["value"] == 3
lat = m["obs"]["serve.latency_s.logreg"]
assert lat["count"] == 3 and lat["p99"] >= lat["p50"] > 0
print(
    f"serve metrics: accepted={m['accepted']} "
    f"latency p50={lat['p50'] * 1e3:.2f}ms p99={lat['p99'] * 1e3:.2f}ms"
)

# -- obs server: /metrics + /healthz over real HTTP -------------------------
server = obs_server.start(0)
try:
    body = urllib.request.urlopen(server.url + "/healthz", timeout=10).read()
    assert body == b"ok\n", body
    text = urllib.request.urlopen(
        server.url + "/metrics", timeout=10
    ).read().decode()
    samples = export.parse_prometheus(text)
    assert samples[("serve_accepted_total", ())] == 3
    assert samples[("serve_queue_depth", ())] == 0
    assert samples[("serve_latency_s_logreg_count", ())] == 3
    assert samples[("serve_latency_s_logreg_bucket", (("le", "+Inf"),))] == 3
    snap = json.loads(
        urllib.request.urlopen(server.url + "/snapshot", timeout=10).read()
    )
    assert snap["flight"]["enabled"], "serving engine left the ring off"
    print(
        f"obs server: /healthz ok, /metrics parsed "
        f"({len(samples)} samples), flight ring on"
    )
finally:
    obs_server.stop()

# -- synthetic SLO breach: tiny queue + burst -> incident JSONL -------------
with tempfile.TemporaryDirectory() as tmp:
    burst_srv = serve.ServingEngine(serve.ServeConfig(
        max_queue=2, max_batch=4,
        slo_rules=(
            slo.SLORule("shed_rate", "serve.shed.queue_full",
                        per="serve.accepted", threshold=0.2),
        ),
        slo_interval_s=0.0,
        incident_dir=os.path.join(tmp, "incidents"),
    ))
    tickets = [burst_srv.submit(q(seed=s, epochs=1)) for s in range(6)]
    shed = sum(not t.accepted for t in tickets)
    burst_srv.drain()
    assert shed == 4, shed
    assert burst_srv.slo.breaches, "burst over a 2-deep queue must breach"
    event = burst_srv.slo.breaches[0]
    assert event["rule"] == "shed_rate" and event["observed"] > 0.2
    header, span_count = slo.validate_incident(event["incident_path"])
    assert header["flight_spans"] == span_count >= 1
    assert header["metrics"]["serve.shed.queue_full"]["value"] == shed
    print(
        f"slo breach: shed {shed}/6, incident "
        f"{os.path.basename(event['incident_path'])} valid "
        f"({span_count} flight spans)"
    )

print("OBS SMOKE OK")
