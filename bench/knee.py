#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate its
server sustains. Run once on the chip when the cell is defined; the
cell's traffic file then fixes its rate at about four fifths of it.

    python bench/knee.py --workload forest_select_open --seed 7 \
        --seconds 20 --rates 2,3,3.5,4,4.5,5

One process sets up once, then offers each rate for ``--seconds`` with
the cell's own arrival schedule, and prints per rate: the queries
completed per second from the window's start to the last completion,
p50 and p90 latency from the due time, and how long the backlog took to
drain after the window closed. A rate is
sustained while completions keep pace with it and the drain stays
short.
"""

import argparse
import json
import os
import sys
import time

STARTED = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", default="forest_select_open")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--rates", default="2,3,3.5,4,4.5,5")
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import jax

    import harness

    cell = harness.resolve(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    harness.device_info(cell.chips, require_chip=True)
    seeds = harness.Seeds(args.seed)
    data = cell.module.generate(cell.config, seeds.table_key())
    jax.block_until_ready(data)
    driver = harness.OpenLoop(cell, data, seeds)
    driver.setup()
    harness.say(f"set-up {time.perf_counter() - STARTED:.3f}s")
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic["rate_per_s"] = rate
        driver.calls, driver.answers = [], []
        t0 = time.perf_counter()
        out = driver.window(args.seconds)
        drain = time.perf_counter() - t0 - args.seconds
        print(json.dumps({"rate_per_s": rate, **out, "drain_s": drain,
                          "lanes": driver.calls}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
