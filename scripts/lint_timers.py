#!/usr/bin/env python
"""Ratchet lint: keep ad-hoc ``time.perf_counter()`` timing out of the
library.

The obs layer (``repro.obs``) is the one sanctioned timing surface —
spans and histograms — so raw ``perf_counter()`` calls are only allowed
where measuring IS the job: ``src/repro/obs/``, ``benchmarks/``,
``tests/``, ``scripts/`` and ``chip_smoke.py``. Everywhere else the call sites that predate
this lint are grandfathered at their current counts (the BASELINE
below); a file may shrink its count but never grow it, and a new file
outside the allowed directories may not introduce any. To bless a
legitimate new call site (there almost never is one — use
``obs.span``/``obs.metrics.observe``), lower-or-update BASELINE in the
same commit and say why.

Usage: python scripts/lint_timers.py   (exit 0 clean, 1 on violations)
"""

from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PATTERN = re.compile(r"\btime\.perf_counter\(\)")

# Directories (relative, prefix-matched) where raw timers are the point.
ALLOWED_DIRS = (
    "src/repro/obs/",
    "benchmarks/",
    "tests/",
    "scripts/",
    "chip_smoke.py",  # reports the set-up walls of its chip run
)

# Never scanned: vendored/seed copies and VCS internals.
SKIPPED_DIRS = (".git", ".wt-seed", "__pycache__", ".pytest_cache")

# Grandfathered call sites, frozen at their pre-lint counts. These
# predate the obs layer's "instrument through repro.obs" rule; each
# already feeds an obs histogram or a result field, so rewriting them
# wholesale buys nothing. The ratchet only moves down.
BASELINE = {
    "examples/matrix_factorization.py": 4,
    "examples/serve_lm.py": 4,
    "src/repro/core/uda.py": 3,
    "src/repro/engine/executor.py": 9,
    "src/repro/engine/probes.py": 6,
    "src/repro/engine/serve.py": 9,
    "src/repro/engine/shard.py": 4,
    "src/repro/launch/train_loop.py": 2,
}


def scan():
    violations = []
    for dirpath, dirnames, filenames in os.walk(ROOT):
        dirnames[:] = [d for d in dirnames if d not in SKIPPED_DIRS]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, ROOT).replace(os.sep, "/")
            if any(rel.startswith(d) for d in ALLOWED_DIRS):
                continue
            with open(path, encoding="utf-8") as f:
                count = len(PATTERN.findall(f.read()))
            if count == 0:
                continue
            allowed = BASELINE.get(rel, 0)
            if count > allowed:
                violations.append((rel, count, allowed))
    return violations


def main() -> int:
    violations = scan()
    if not violations:
        print("lint_timers: ok (no new raw perf_counter call sites)")
        return 0
    for rel, count, allowed in sorted(violations):
        print(
            f"lint_timers: {rel}: {count} time.perf_counter() call(s), "
            f"baseline allows {allowed} — time through repro.obs "
            f"(obs.span / obs.metrics.observe) instead",
            file=sys.stderr,
        )
    return 1


if __name__ == "__main__":
    sys.exit(main())
