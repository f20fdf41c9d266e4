"""The one rule for JAX's persistent compilation cache.

Every entry point that builds an engine calls `enable_compile_cache()`
before its first compile (frontend and worker mains, bench.py, the
profiling tools, the planner profilers, chip_smoke.py, tests/conftest.py).
A server restart then reloads its step programs instead of recompiling
them.

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing here sets
  a directory.
- Otherwise: one fixed, git-ignored directory inside the checkout.  The
  path is part of what a deployment keeps between runs, so it is never
  derived from /tmp, a pid, a temporary name or the clock.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(name: str = "serve") -> str:
    """Turn the persistent cache on; returns the directory in effect.

    `name` picks the subdirectory of `DEFAULT_DIR` ("serve" for programs,
    "tests" for the suite, whose virtual-CPU executables would otherwise
    crowd the servers' entries).  Ignored when the environment variable
    names the directory."""
    import jax

    # Keep every program, however quickly it compiled (JAX's default
    # skips those under 1 s): a restarted server wants all of them, and
    # the test suite, which builds hundreds of engines over identical
    # HLO, runs a tenth faster from a cold cache when the small ones
    # dedupe too (tests/test_engine.py: 60.5 s at a 0.3 s threshold,
    # 53.8 s at 0).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A Pallas kernel travels inside its custom call as serialized MLIR,
    # locations included, and JAX strips locations from the cache key
    # only at the top level.  With full tracebacks in them (the default)
    # every program that holds a kernel is keyed by the Python call
    # stack of its entry point: on the chip a worker found none of the
    # 15 step programs the frontend had just compiled (PR 21).
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    path = os.path.join(DEFAULT_DIR, name)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
