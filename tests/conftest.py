"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's GPU-free test strategy (SURVEY.md §4): all
distributed-sharding tests run on `--xla_force_host_platform_device_count=8`
CPU devices, so CI needs no TPU.  Must run before any `import jax`.
"""

import os

# Debug-mode thread-affinity contracts (runtime/contracts.py): the
# decorators on EngineCore step/seal/export internals, the block-manager
# entry points, SloMonitor.tick and KvCacheMetrics sampling assert
# caller-thread identity for the whole suite.  Must be set before any
# dynamo_tpu import — decoration reads the env var at import time (the
# zero-cost-off guarantee).  Respect an explicit =0 so the pinned
# counter tests can be re-run contracts-off for A/B.
os.environ.setdefault("DYNAMO_CONTRACTS", "1")

# The suite stays on the virtual CPU mesh whatever the machine holds:
# one chip cannot host the 8-way sharding tests.  Both variables must be
# set before jax is imported.
import re

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", ""),
)
os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()

# Persistent XLA compilation cache (runtime/compile_cache.py): the
# suite builds hundreds of EngineCore instances whose jitted steps lower
# to IDENTICAL HLO, and each new jax.jit instance recompiles it —
# backend-compile dedupe via the disk cache cuts suite wall-time ~35%
# even within one cold run (and more when the driver re-runs tier-1 in
# the same container).  Keys on HLO hash, so test semantics are
# untouched; engine-side counters (EngineStepCounters.xla_cache_misses)
# count traced shapes, not backend compiles, and are unaffected.
from dynamo_tpu.runtime.compile_cache import enable_compile_cache  # noqa: E402

TEST_CACHE_DIR = enable_compile_cache("tests")


# -- thread-leak guard -----------------------------------------------------
# Non-daemon threads that outlive their test accumulate silently across
# the suite (an unstopped HbmPoller would be daemon, but kv-offload /
# kv-window-fetch ThreadPoolExecutor workers are NOT) and can wedge
# interpreter exit.  Cheap session-scoped check: compare the non-daemon
# census at session start and end; fail loudly — with names — above an
# allowance that covers executor workers parked until their pool is
# garbage-collected.

import gc  # noqa: E402
import threading  # noqa: E402
import time as _time  # noqa: E402

import pytest  # noqa: E402

# Idle ThreadPoolExecutor workers exit only when their executor is
# collected (weakref wakeup), so the census depends on GC timing; the
# allowance absorbs that churn while still catching a real per-test
# leak (which grows with the test count, not the pool count).
THREAD_LEAK_ALLOWANCE = int(os.environ.get("DYNAMO_THREAD_LEAK_MAX", "24"))


@pytest.fixture(autouse=True, scope="session")
def _thread_leak_guard():
    baseline = {t.ident for t in threading.enumerate() if not t.daemon}
    yield
    gc.collect()  # release executor threads owned by dead engines
    deadline = _time.monotonic() + 2.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if not t.daemon and t.is_alive()
                  and t.ident not in baseline]
        if (len(leaked) <= THREAD_LEAK_ALLOWANCE
                or _time.monotonic() >= deadline):
            break
        _time.sleep(0.1)
    if len(leaked) > THREAD_LEAK_ALLOWANCE:
        names = sorted(t.name for t in leaked)
        pytest.fail(
            f"{len(leaked)} non-daemon thread(s) leaked across the suite "
            f"(allowance {THREAD_LEAK_ALLOWANCE}): {names[:40]}",
            pytrace=False)
