#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python bench/tests/record_trace.py <out_dir>

On a TPU: one 2-epoch Forest ``logreg`` fit under ``bench.fit``, a wait
under ``bench.await_arrival``, and one fused serving batch of two ``svm``
queries under ``bench.pump``, all inside ``bench.window``, traced with
the harness's profiler options. The trace is written to
``<out_dir>/small.xplane.pb`` without its ``/host:metadata`` plane (the
compiled programs' HLO, about 640 KB, which the reduction never reads);
copy it to ``bench/tests/data/``.
"""

import glob
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _varint(b: bytes, i: int):
    value = shift = 0
    while True:
        c = b[i]
        i += 1
        value |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return value, i


def _fields(b: bytes):
    """(field number, wire type, raw bytes, payload) of each top-level
    field of a protobuf message, in order."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        wire = key & 7
        payload = b""
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(b, i)
            payload = b[i:i + n]
            i += n
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, wire, b[start:i], payload


def drop_plane(xspace: bytes, name: bytes) -> bytes:
    """An XSpace without the plane of that name (XSpace.planes is field
    1, XPlane.name field 2)."""
    out = bytearray()
    for field, wire, raw, payload in _fields(xspace):
        if field == 1 and wire == 2 and any(
            f == 2 and p == name for f, _, _, p in _fields(payload)
        ):
            continue
        out += raw
    return bytes(out)


def main(out_dir: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import jax

    import harness
    from repro import engine
    from repro.launch.serve import make_analytics_server

    if jax.devices()[0].platform == "cpu":
        print("no accelerator", file=sys.stderr)
        return 2
    cell = harness.resolve("forest_logreg_fit")
    data = cell.module.generate(cell.config, jax.random.PRNGKey(0))
    args = cell.module.task_args(cell.config, "logreg")

    def query(task, seed):
        return engine.AnalyticsQuery(task=task, data=data, task_args=args,
                                     epochs=2, tolerance=0.0, seed=seed)

    eng = engine.Engine()
    jax.block_until_ready(eng.run(query("logreg", 1)).model)
    srv = make_analytics_server(max_batch=2)
    for _ in range(2):
        srv.submit(query("svm", 2))
    srv.pump()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with harness.span("bench.window"):
        with harness.span("bench.fit"):
            jax.block_until_ready(eng.run(query("logreg", 3)).model)
        with harness.span("bench.await_arrival"):
            time.sleep(0.05)
        for s in (4, 5):
            srv.submit(query("svm", s))
        with harness.span("bench.pump"):
            srv.pump()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        small = drop_plane(f.read(), b"/host:metadata")
    with open(os.path.join(out_dir, "small.xplane.pb"), "wb") as f:
        f.write(small)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
