import os
import sys

# The benchmark's tests run on the CPU, at sizes the CPU holds, and leave
# no compilation cache behind; the program and the harness are imported
# from the checkout.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# a size of each configuration that a test run holds: the published
# width (54 features), fewer rows
SMALL = {
    "forest_covtype": {"rows": 1024},
}
