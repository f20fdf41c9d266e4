"""Memory-plane telemetry (KvCacheMetrics/HbmPoller), the real engine's
prefix-cache hit rate, and the metrics-exposition satellites (label
escaping, scrape-vs-observe locking)."""

import re
import threading

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.llm.block_manager.pool import BlockPool
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.runtime.metrics import (
    Counter, Gauge, HbmPoller, Histogram, KvCacheMetrics, MetricsRegistry)

TINY = mcfg.get_config("tiny-test")


# -- Prometheus label escaping (satellite) -----------------------------------


def test_label_value_escaping_round_trip():
    """Label values containing `"`, `\\`, and newlines must emit valid
    exposition that parses back to the original strings."""
    g = Gauge("t", "t")
    nasty = 'quo"te', "back\\slash", "new\nline", 'all\\"of\nit'
    for i, v in enumerate(nasty):
        g.set(float(i), labels={"k": v})
    lines = [ln for ln in g.expose() if not ln.startswith("#")]
    assert len(lines) == len(nasty)
    label_re = re.compile(r'^t\{k="((?:[^"\\]|\\.)*)"\} ')
    parsed = set()
    for ln in lines:
        m = label_re.match(ln)
        assert m, f"invalid exposition line: {ln!r}"
        raw = m.group(1)
        assert "\n" not in raw  # newline must be escaped, not literal
        parsed.add(raw.replace("\\n", "\n").replace('\\"', '"')
                   .replace("\\\\", "\\"))
    assert parsed == set(nasty)


def test_histogram_label_escaping():
    h = Histogram("h", "h", buckets=(1.0,))
    h.observe(0.5, labels={"model": 'a"b'})
    text = "\n".join(h.expose())
    assert 'model="a\\"b"' in text


# -- expose under concurrent mutation (satellite) ----------------------------


def test_histogram_expose_consistent_under_concurrent_observe():
    """A scrape racing observe() must never emit torn cumulative counts
    (bucket cum exceeding _count, or non-monotone cum)."""
    h = Histogram("h", "h", buckets=(0.001, 0.01, 0.1, 1.0))
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            h.observe((i % 40) / 10.0, labels={"m": str(i % 3)})
            i += 1

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(300):
            lines = h.expose()
            cums = {}
            counts = {}
            for ln in lines:
                if ln.startswith("#"):
                    continue
                name_labels, _, v = ln.rpartition(" ")
                if name_labels.startswith("h_bucket"):
                    key = re.sub(r',?le="[^"]*"', "", name_labels)
                    cum = float(v)
                    assert cum >= cums.get(key, 0.0), lines
                    cums[key] = cum
                elif name_labels.startswith("h_count"):
                    counts[name_labels] = float(v)
            for key, total in counts.items():
                bkey = key.replace("h_count", "h_bucket")
                assert cums.get(bkey, 0.0) == total, lines
    finally:
        stop.set()
        t.join()


def test_counter_gauge_expose_under_concurrent_mutation():
    c, g = Counter("c", "c"), Gauge("g", "g")
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            c.inc(labels={"k": str(i % 5)})
            g.set(i, labels={"k": str(i % 5)})
            i += 1

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(300):
            c.expose()
            g.expose()
    finally:
        stop.set()
        t.join()


# -- KvCacheMetrics over a real BlockPool ------------------------------------


def test_kv_metrics_block_pool_alloc_evict_release_cycle():
    registry = MetricsRegistry()
    kvm = KvCacheMetrics(registry)
    pool = BlockPool(4, name="G1-device", reserve_null=True)  # 3 usable

    [a] = pool.allocate(1)
    pool.register(a, 0xA)
    kvm.observe_pool(pool, "device")
    labels = {"tier": "device", "pool": "G1-device"}
    assert kvm.pool_capacity.value(labels) == 4
    assert kvm.pool_active.value(labels) == 1
    assert kvm.pool_free.value(labels) == 2
    assert kvm.evictions.value(labels) == 0

    pool.release([a])                      # → inactive (reusable)
    kvm.observe_pool(pool, "device")
    assert kvm.pool_active.value(labels) == 0
    assert kvm.pool_reusable.value(labels) == 3

    pool.allocate(3)                       # forces LRU eviction of 0xA
    assert pool.evictions == 1
    kvm.observe_pool(pool, "device")
    assert kvm.evictions.value(labels) == 1
    # Counter is delta-tracked: re-observing the same cumulative value
    # must not double count.
    kvm.observe_pool(pool, "device")
    assert kvm.evictions.value(labels) == 1

    text = registry.expose()
    for series in ("dynamo_kv_pool_capacity_blocks",
                   "dynamo_kv_pool_active_blocks",
                   "dynamo_kv_pool_reusable_blocks",
                   "dynamo_kv_pool_free_blocks",
                   "dynamo_kv_evictions_total"):
        assert f'{series}{{pool="G1-device",tier="device"}}' in text


# -- real engine: prefix hit rate + pool series ------------------------------


def _engine(**kw) -> EngineCore:
    defaults = dict(
        model=TINY,
        num_blocks=64,
        enable_prefix_cache=True,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=16,
            max_prefill_chunk=16,
            decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16)),
    )
    defaults.update(kw)
    return EngineCore(EngineConfig(**defaults))


def _run(core, max_steps=600):
    outputs, finished = {}, {}
    for _ in range(max_steps):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
            if d.finished:
                finished[d.request_id] = d.finish_reason
        if not core._requests:
            break
    return outputs, finished


def test_real_engine_reports_prefix_cache_hit_rate_and_pool_series():
    """The acceptance pin: after a prefix-reuse workload the REAL engine
    (not the mocker) reports nonzero gpu_prefix_cache_hit_rate in
    ForwardPassMetrics and emits dynamo_kv_pool_* series."""
    core = _engine(decode_window=1)
    prompt = list(range(1, 25))            # 24 tokens → 3 sealed blocks

    core.add_request("a", prompt, SamplingParams(max_tokens=4))
    _run(core)
    assert core.metrics.kv_stats.gpu_prefix_cache_hit_rate == 0.0

    core.add_request("b", prompt, SamplingParams(max_tokens=4))
    _run(core)
    ks = core.metrics.kv_stats
    assert ks.gpu_prefix_cache_hit_rate > 0.3, ks
    # Request b's admission matched a's sealed prompt blocks: 23 of its
    # 24 prompt tokens skipped prefill (last one always recomputes).
    assert core.scheduler.prefix_hit_tokens == 23
    assert core.scheduler.prefix_miss_tokens == 25

    registry = MetricsRegistry()
    kvm = KvCacheMetrics(registry)
    kvm.observe_engine(core)
    text = registry.expose()
    assert ('dynamo_kv_pool_capacity_blocks{pool="G1-device",'
            'tier="device"} 64.0') in text
    assert ('dynamo_kv_prefix_cache_hits_tokens{pool="G1-device",'
            'tier="device"} 23.0') in text
    assert ('dynamo_kv_prefix_cache_misses_tokens{pool="G1-device",'
            'tier="device"} 25.0') in text
    # Sealed blocks stay resident (inactive) after finish → reusable.
    labels = {"tier": "device", "pool": "G1-device"}
    assert kvm.pool_reusable.value(labels) > 0


def test_host_tier_pool_series_after_offload():
    """G2 host tier shows up under tier="host" once sized > 0."""
    core = _engine(decode_window=1, host_blocks=8)
    registry = MetricsRegistry()
    kvm = KvCacheMetrics(registry)
    kvm.observe_engine(core)
    text = registry.expose()
    assert 'dynamo_kv_pool_capacity_blocks{pool="G2-host",tier="host"} 8.0' \
        in text
    close = getattr(core.allocator.manager, "close", None)
    if close:
        close()


def test_plain_allocator_engine_still_emits_device_series():
    core = _engine(enable_prefix_cache=False, decode_window=1)
    core.add_request("a", [1, 2, 3, 4], SamplingParams(max_tokens=2))
    _run(core)
    registry = MetricsRegistry()
    kvm = KvCacheMetrics(registry)
    kvm.observe_engine(core)
    text = registry.expose()
    assert 'dynamo_kv_pool_capacity_blocks{pool="plain",tier="device"} 63.0' \
        in text


# -- steady decode window pays nothing for telemetry -------------------------


def test_kv_telemetry_steady_window_zero_overhead():
    """The acceptance pin: per-step memory-plane sampling (hotter than
    any real scrape cadence) adds 0 host syncs and 0 dispatches to the
    steady decode window — EngineStepCounters.delta discipline."""

    def steady_run(observe: bool):
        core = _engine(
            decode_window=2, window_pipeline_depth=2, num_blocks=128,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=128,
                decode_buckets=(1, 2, 4, 8), prefill_buckets=(16, 128)))
        kvm = KvCacheMetrics(MetricsRegistry())
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):
            core.step()
        assert core._inflight, "window pipeline not running after warmup"
        base = core.counters.snapshot()
        for _ in range(20):
            core.step()
            if observe:
                kvm.observe_engine(core)
        return core.counters.delta(base)

    d_off = steady_run(False)
    d_on = steady_run(True)
    assert d_on["host_syncs"] == d_off["host_syncs"], (d_on, d_off)
    for key in ("window_dispatches", "single_step_dispatches",
                "prefill_dispatches", "h2d_uploads", "xla_cache_misses"):
        assert d_on[key] == d_off[key], (key, d_on, d_off)


# -- HBM poller --------------------------------------------------------------


def test_hbm_poller_without_device_stats_emits_nothing():
    """CPU backend (no device memory_stats): the poll reports 0 devices
    and writes NO dynamo_hbm_* sample — host RSS is not device memory."""
    registry = MetricsRegistry()
    kvm = KvCacheMetrics(registry)
    poller = HbmPoller(kvm, interval=999.0)
    assert poller.poll_once() == 0
    assert not [ln for ln in registry.expose().splitlines()
                if ln.startswith("dynamo_hbm_")]
