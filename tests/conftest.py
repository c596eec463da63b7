import os
import sys

# Tests run on the single real CPU device (the dry-run sets its own device
# count in a subprocess); keep XLA quiet and deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# JAX's own switch for the persistent compilation cache: tests (and the
# subprocesses they start) leave no cache files in the checkout
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:
    import hypothesis  # noqa: F401
except ImportError:  # container image has no hypothesis; use the shim
    sys.path.insert(0, os.path.dirname(__file__))
    import _hypothesis_shim

    _hypothesis_shim.install()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Process-wide observability state must not leak between tests:
    snapshot/restore the shared retrace tally, and force the tracer off,
    the operational tier torn down (flight ring uninstalled, obs HTTP
    server stopped, recent SLO breaches cleared) and the metrics
    registry empty afterwards (a test that enables tracing, starts the
    server or bumps counters must not change what the next one sees)."""
    from repro import obs
    from repro.core import tracecount

    tally = tracecount.snapshot()
    yield
    tracecount.restore(tally)
    obs.reset_operational()
    obs.reset_metrics()
