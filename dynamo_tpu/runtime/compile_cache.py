"""The one rule for JAX's persistent compilation cache.

Every entry point that builds an engine calls `enable_compile_cache()`
before its first compile (frontend and worker mains, the planner
profilers, tools/profile_trace.py, chip_smoke.py, chipbench's serving
child, tests/conftest.py).
A restarted server then compiles nothing it has compiled before.  What it
still pays depends on the program.  This cache is keyed by the lowered
module, so a program found here is traced and lowered again first and
then read (107 step programs of Mistral-7B at 16 layers: trace 97 s +
lower 97 s + read 34 s of a 275 s start, ledger PR 24).  The step
programs of the entry points that serve therefore go through
`program_store.py`, which lives in a subdirectory of this cache's
directory and finds the executable by shape: a restart pays the read
alone (`stage="store_read"`), and this cache serves the small programs
and every program's first compile.

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing here sets
  a directory.
- Otherwise: one fixed, git-ignored directory inside the checkout.  The
  path is part of what a deployment keeps between runs, so it is never
  derived from /tmp, a pid, a temporary name or the clock.

The same call starts the process's program-build accounting: JAX times
every part of building a program itself (tracing to a jaxpr, lowering to
MLIR, the backend compile, the read from this cache) and publishes it
through `jax.monitoring`; one listener, registered once, adds it up
(`program_builds()`, `metrics_lines()` for the worker's `/metrics`).  The
program store reports into the same accounting (`note_program_store`).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# jax.monitoring duration events -> stage of a program build.  `backend`
# is JAX's backend_compile_duration, which CONTAINS the cache read of a
# hit: a reader that wants compile time alone subtracts `cache_read`.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# Process-wide by nature (jax.monitoring's listeners are): a compile may
# run on any thread, so every add holds the lock.
_lock = threading.Lock()
_seconds: Dict[str, float] = {
    **{stage: 0.0 for stage in _STAGES.values()},
    "store_read": 0.0, "store_wait": 0.0}
_counts: Dict[str, int] = {"builds": 0, "cache_hits": 0}
_store: Dict[str, int] = {"hits": 0, "misses": 0, "errors": 0,
                         "prefetched": 0, "prefetch_unclaimed": 0}
_listening = False
# Trace events nest: a jitted function's trace holds the traces of every
# jitted function it calls (each `jnp` operation is one), and JAX reports
# all of them, the inner ones first.  Summed as they come they count the
# same seconds several times over (3.5 times in a small example, and more
# than the process had lived in a 16-layer model's set-up).  Per thread,
# the events that ended since this one began are its children: their
# seconds are taken off it.
_trace_ends = threading.local()
_thread_hits = threading.local()
_TRACE_KEPT = 65536      # finished events a thread remembers, at most


def _own_trace_seconds(duration_secs: float) -> float:
    now = time.monotonic()
    ends = getattr(_trace_ends, "stack", None)
    if ends is None:
        ends = _trace_ends.stack = []
    began, inner = now - duration_secs, 0.0
    while ends and ends[-1][0] >= began:
        inner += ends.pop()[1]
    if len(ends) >= _TRACE_KEPT:
        del ends[:_TRACE_KEPT // 2]
    ends.append((now, duration_secs))
    return max(0.0, duration_secs - inner)


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    if stage == "trace":
        duration_secs = _own_trace_seconds(duration_secs)
    with _lock:
        _seconds[stage] += duration_secs
        if stage == "backend":
            _counts["builds"] += 1


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _thread_hits.n = cache_hits_on_this_thread() + 1
        with _lock:
            _counts["cache_hits"] += 1


def cache_hits_on_this_thread() -> int:
    """Persistent-cache hits of compiles that ran on the calling thread
    (JAX reports a hit on the thread that compiles): read before and after
    a compile, it says whether that executable was read from the cache."""
    return getattr(_thread_hits, "n", 0)


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def note_program_store(outcome: Optional[str] = None, count: int = 1,
                       read_seconds: float = 0.0,
                       wait_seconds: float = 0.0) -> None:
    """What the program store (`program_store.py`) did.  Outcomes of a
    shape's first call: a `hits` (served from the store, whichever thread
    loaded it; also a `prefetched` where the read-ahead had loaded it or
    was loading it), a `misses` (compiled, as without a store) or an
    `errors` (an entry or the directory could not be used);
    `prefetch_unclaimed` counts what the read-ahead loaded and released
    unasked at its join.  `read_seconds` (`stage="store_read"`): file
    read, unpack, deserialize and load of one entry, on whichever thread
    spent them, so with several threads they can sum to more than the
    wall clock.  `wait_seconds` (`stage="store_wait"`): what a first call
    spent in the store on the calling thread, waiting for a load under
    way or loading itself."""
    with _lock:
        if outcome is not None:
            _store[outcome] += count
        _seconds["store_read"] += read_seconds
        _seconds["store_wait"] += wait_seconds


def program_builds() -> dict:
    """What building programs has cost this process since
    `enable_compile_cache()`: seconds per stage (`trace`, nested traces
    counted once; `lower`; `backend`; `cache_read`), `builds` (backend-
    compile events: programs compiled or read back) and persistent-cache
    `cache_hits`; `builds - cache_hits` programs went through the
    compiler.  `store_read` seconds and `program_store` count the step
    programs that came from, or went to, the program store."""
    with _lock:
        return {"seconds": dict(_seconds), **_counts,
                "program_store": dict(_store)}


def metrics_lines() -> List[str]:
    """Prometheus text lines of `program_builds()` for the worker's
    `/metrics`; empty in a process that never enabled the cache (mocker
    workers build no program)."""
    if not _listening:
        return []
    b = program_builds()
    return [
        *(f'dynamo_worker_program_build_seconds_total{{stage="{stage}"}} '
          f'{secs:.6f}' for stage, secs in b["seconds"].items()),
        f'dynamo_worker_program_builds_total {b["builds"]}',
        f'dynamo_worker_compile_cache_hits_total {b["cache_hits"]}',
        *(f'dynamo_worker_program_store_{outcome}_total {n}'
          for outcome, n in b["program_store"].items()),
    ]


def enable_compile_cache(name: str = "serve") -> str:
    """Turn the persistent cache on; returns the directory in effect.

    `name` picks the subdirectory of `DEFAULT_DIR` ("serve" for programs,
    "tests" for the suite, whose virtual-CPU executables would otherwise
    crowd the servers' entries).  Ignored when the environment variable
    names the directory."""
    import jax

    _listen()
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
