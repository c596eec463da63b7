#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run
generates its table from ``--seed`` on the device, sets up and warms up
the program (counted in ``setup_s``), measures for ``--seconds``, and
then checks a seeded sample of the answers against the configuration's
plain reference. The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a ``jax.profiler`` trace of the window), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``, each compared number
beside its limit. The checks are also the last lines of stderr.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result. JAX's persistent compilation cache
lives in ``.jax_cache/`` inside the checkout, so only a cell's first run
in a checkout compiles.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    # a fixed path inside the checkout: the path is part of the cache key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    # persist every program, however small or quick to compile
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    # libtpu logs to /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import harness

    cell = harness.resolve(args.workload)
    try:
        result, reasons = harness.run(
            cell, args.seed, args.seconds, bool(args.trace), started=STARTED,
        )
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    for r in reasons:
        harness.say(f"not correct: {r}")
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
