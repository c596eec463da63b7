"""JAX's persistent compilation cache, at a path that outlives a process.

The ``PlanStore`` eliminates re-*measuring* and re-*planning* across
processes; this module keeps the XLA executables as well, so a fresh
process deserializes compiled programs instead of re-running XLA. The
``Engine``/``ServingEngine`` constructors call :func:`maybe_enable`.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when it is set. JAX reads the variable
  itself, and this module sets no directory of its own.
* Otherwise a fixed ``<checkout>/.jax_cache`` (gitignored). The path is
  part of the cache key, so it must not move between runs.

JAX's own switch, ``JAX_ENABLE_COMPILATION_CACHE=false``, turns the cache
off (the test suite does, so that tests leave no cache files behind).

The thresholds are zeroed: the engine's jitted epoch functions are small
(milliseconds of XLA time each), below jax's default "worth persisting"
cutoffs, and the serving cold-start they add up to is exactly what the
cache exists to remove.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: this file is <checkout>/src/repro/engine/xla_cache.py
DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))

# path the cache was enabled for (None = not enabled); enable-once per
# process: jax's cache dir is global config, not per-engine state
_state: Dict[str, Optional[str]] = {"path": None, "error": None}


def maybe_enable(env: Optional[dict] = None) -> bool:
    """Enable the persistent compilation cache unless JAX's switch turns
    it off. Returns True when the cache is (already) enabled. Never
    raises: a failure degrades to normal in-process compilation,
    recorded in ``status()["error"]`` (a directory JAX cannot write
    makes JAX itself warn and compile without the cache)."""
    if not jax.config.jax_enable_compilation_cache:
        return False
    from_env = (os.environ if env is None else env).get(ENV_VAR, "").strip()
    path = from_env or DEFAULT_DIR
    if _state["path"] == path:
        return True
    try:
        if not from_env:
            # JAX makes the directory when it first writes to it, so
            # importing the engine (which builds a default Engine)
            # touches no file
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax memoizes its cache object on first compile: a process that
        # already jitted something (planner probes, warmups) would
        # silently keep running cache-less without this reset
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
        _state["path"] = path
        _state["error"] = None
        return True
    except Exception as e:  # noqa: BLE001 - optional optimization
        _state["error"] = f"{type(e).__name__}: {e}"
        return False


def status() -> Dict[str, Optional[str]]:
    return dict(_state)
