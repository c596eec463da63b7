#!/usr/bin/env python3
"""The control of a cell's check: the plain reference put in the
program's place, computed in bfloat16, the precision below the float32
that the configurations state. Its answers go through the same
comparison as a run's, and must come out as not correct.

    python bench/control.py --workload forest_logreg_fit --seeds 21,22,23

For each seed it makes the cell's table as a run with that seed does,
takes the ordering the planner chooses for the cell's query, and prints
one JSON line with the gaps the comparison reads and the cell's limits.
The benchmark's own runs never run it.
"""

import argparse
import json
import os
import sys

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_answers(harness, cell, data, seeds, dtype=jnp.bfloat16):
    """Answers as the program would give them, made by the reference in
    ``dtype`` for the seeds a run's window would use."""
    from repro import engine

    driver = harness.Driver(cell, data, seeds)
    task, epochs = driver.task, int(cell.traffic["epochs"])
    count = int(cell.traffic["check_answers"])
    query_seeds = seeds.ints(3, count)
    ordering = engine.Engine().explain(
        driver.query(query_seeds[0])).chosen.ordering
    out = []
    for s in query_seeds:
        model = cell.module.reference_fit(cell.config, data, task, s, epochs,
                                          ordering, dtype=dtype)
        loss = cell.module.reference_loss(cell.config, data, task, model,
                                          dtype=dtype)
        out.append(harness.Answer(s, model, loss, ordering, epochs,
                                  f"reference in {jnp.dtype(dtype).name}",
                                  True))
    return out


def readings(harness, cell, seed: int, dtype=jnp.bfloat16) -> dict:
    import jax

    seeds = harness.Seeds(seed)
    data = cell.module.generate(cell.config, seeds.table_key())
    jax.block_until_ready(data)
    worst = harness.compare(cell, data,
                            control_answers(harness, cell, data, seeds, dtype))
    return {"workload": cell.name, "seed": seed, **worst,
            "limits": cell.limits}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="21,22,23")
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import harness

    cell = harness.resolve(args.workload)
    harness.device_info(cell.chips, require_chip=True)
    for s in args.seeds.split(","):
        print(json.dumps(readings(harness, cell, int(s))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
