"""Chaos suite (ISSUE 15): elastic serving under worker death, planned
drain, control-plane partition, and QoS pressure.

The discipline: every scenario asserts on MACHINE-CHECKABLE evidence —
request outcomes (`dynamo_request_outcomes_total`), flight-recorder dump
CONTENTS (tools/trace_merge.load_flight_dump), reaped
`status_endpoints/` registrations (tools/dynamo_top.collect), fetcher
plane counters — never on log text.

In-process engine tests share tiny-test geometry with
tests/test_prefix_share.py (same EngineConfig → same compiled shapes →
compile-cache reuse inside the tier-1 budget); the e2e scenarios run
mocker workers as real OS processes (cheap: no jax engine build).
"""

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from dynamo_tpu.engine.engine import (
    EngineConfig, EngineCore, InferenceEngine, TokenDelta)
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.llm.block_manager.prefix_share import (
    MIGRATE_ANNOTATION, PrefixFetcher, PrefixShareClient)
from dynamo_tpu.llm.block_manager.transfer import (
    KV_BLOCKS_ENDPOINT, make_kv_blocks_handler)
from dynamo_tpu.llm.drain import (
    DRAIN_REFUSAL, DrainableService, WorkerDrainingError)
from dynamo_tpu.llm.migration import MigrationClient
from dynamo_tpu.llm.preprocessor import PreprocessedRequest
from dynamo_tpu.llm.service import LocalEngineClient, priority_of
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.runtime import flight_recorder
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.rpc import RpcClient, RpcError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = mcfg.get_config("tiny-test")
BS = 8
LONG_PROMPT = list(range(1, 36))   # 4 sealed blocks + 3-token tail


def _core(host_blocks=0, num_blocks=64):
    # test_prefix_share's exact tiny geometry (compile-cache reuse).
    return EngineCore(EngineConfig(
        model=TINY, num_blocks=num_blocks, host_blocks=host_blocks,
        scheduler=SchedulerConfig(
            max_seqs=4, block_size=BS, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(1, 2, 4), prefill_buckets=(8, 16))))


class _Worker:
    """One in-process worker: engine + RPC server with kv_blocks, plus
    the device transfer plane (ISSUE 16: the worker ALWAYS starts one —
    local fabric when pjrt cross-host transfer is absent — so drain
    migration rides device-direct instead of the host-staged wire)."""

    def __init__(self, **core_kw):
        self._core_kw = core_kw

    async def start(self):
        from dynamo_tpu.llm.block_manager.device_transfer import (
            KV_OFFER_ENDPOINT, KV_PULLED_ENDPOINT, KvTransferPlane)
        from dynamo_tpu.runtime.rpc import RpcServer

        self.engine = InferenceEngine(_core(**self._core_kw))
        await self.engine.start()
        self.client = LocalEngineClient(self.engine)
        self.plane = KvTransferPlane(self.engine)
        self.plane.start()
        self.rpc = RpcServer()
        self.rpc.register(KV_BLOCKS_ENDPOINT,
                          make_kv_blocks_handler(self.engine))
        self.rpc.register(KV_OFFER_ENDPOINT,
                          self.plane.make_offer_handler())
        self.rpc.register(KV_PULLED_ENDPOINT,
                          self.plane.make_pulled_handler())
        self.address = await self.rpc.start()
        return self

    async def stop(self):
        await self.rpc.stop()
        self.plane.stop()
        await self.engine.stop()


def _run(coro, timeout=180):
    return asyncio.run(asyncio.wait_for(coro, timeout))


async def _collect(client, rid, prompt, sampling, annotations=None):
    req = PreprocessedRequest(request_id=rid, model="m",
                              token_ids=list(prompt), sampling=sampling,
                              annotations=dict(annotations or {}))
    out = []
    async for d in client.generate(req):
        out.extend(d.token_ids)
        if d.finished:
            assert d.finish_reason is not None
            assert d.finish_reason.value != "error"
            break
    return out


# ---------------------------------------------------------------------------
# Drain-migration: byte-identical streams, KV carried over kv_blocks


class _FleetRouter:
    """Two-worker routing stub: the draining worker until it drains,
    the survivor after (what the real instance-set watcher does when the
    drained worker's lease revokes)."""

    def __init__(self, drainable, survivor):
        self.drainable = drainable
        self.survivor = survivor

    async def generate(self, request):
        target = (self.survivor if self.drainable.draining
                  else self.drainable)
        async for d in target.generate(request):
            yield d


def _drain_scenario(sampling, drain_after_tokens):
    """Run the drain-migration scenario; returns (reference_tokens,
    migrated_tokens, fetcher, drainable, sched_b)."""

    async def main():
        wa = await _Worker().start()
        wb = await _Worker().start()
        rpc = RpcClient(wa.address)
        try:
            want = await _collect(wa.client, "ref", LONG_PROMPT, sampling)

            drainable = DrainableService(wa.client, kv_address=wa.address,
                                         block_size=BS)
            fetcher = PrefixFetcher(wb.engine, lambda a: rpc, BS,
                                    plane=wb.plane)
            survivor = PrefixShareClient(wb.client, fetcher)
            mc = MigrationClient(_FleetRouter(drainable, survivor),
                                 migration_limit=3, retry_delay=0.001)

            req = PreprocessedRequest(request_id="r1", model="m",
                                      token_ids=list(LONG_PROMPT),
                                      sampling=sampling)
            got = []
            drained = [False]
            async for d in mc.generate(req):
                got.extend(d.token_ids)
                if len(got) >= drain_after_tokens and not drained[0]:
                    drained[0] = True
                    # Planned drain mid-stream: the worker hands the
                    # request off with its KV; the client stream must
                    # not notice.
                    asyncio.ensure_future(drainable.drain(20.0))
                if d.finished:
                    break
            return want, got, fetcher, drainable, wb.engine.core.scheduler
        finally:
            await rpc.close()
            await wa.stop()
            await wb.stop()

    return _run(main())


def test_drain_migration_byte_identical_greedy():
    """A greedy stream handed off mid-decode is byte-identical to
    uninterrupted serving, and the KV moved over the kv_blocks plane:
    blocks pulled > 0, re-prefill fallbacks == 0 (the ISSUE 15
    acceptance pin)."""
    want, got, fetcher, drainable, sched_b = _drain_scenario(
        SamplingParams(max_tokens=20), drain_after_tokens=6)
    assert got == want, (got, want)
    assert drainable.migrated_out == 1
    # Plane counters pinned: KV crossed the wire, and the happy path
    # never fell back to re-prefill.
    assert fetcher.pulled_blocks > 0
    assert fetcher.fallbacks == 0
    assert fetcher.migrated_in == 1
    # ISSUE 16 satellite: the drain handoff rode the DEVICE plane —
    # every worker now starts a KvTransferPlane (local fabric when pjrt
    # is absent), so the carried KV moved device-direct, not host-staged.
    assert fetcher.device_pulled_blocks > 0
    # The survivor prefix-matched the carried KV at admission: it
    # prefilled only the unsealed tail, not the whole stream.
    assert sched_b.prefix_hit_tokens >= 4 * BS


def test_drain_migration_seeded_stream_keeps_contract():
    """A SEEDED stochastic stream survives the handoff byte-identically:
    SamplingParams.seed_offset keeps the (seed, token-index) law on the
    resuming worker."""
    want, got, fetcher, _, _ = _drain_scenario(
        SamplingParams(max_tokens=16, temperature=0.8, seed=1234),
        drain_after_tokens=5)
    assert got == want, (got, want)
    assert fetcher.fallbacks == 0


def test_drain_refusal_is_retryable_and_idle_drain_instant():
    """New admissions during a drain are refused with the retryable
    marker; an idle worker drains instantly."""

    class _Dead:
        async def generate(self, request):
            raise AssertionError("must not be reached")
            yield  # pragma: no cover

    async def main():
        d = DrainableService(_Dead(), block_size=BS)
        t0 = time.monotonic()
        assert await d.drain(5.0) is True
        assert time.monotonic() - t0 < 1.0
        with pytest.raises(WorkerDrainingError) as ei:
            async for _ in d.generate(PreprocessedRequest(
                    request_id="x", model="m", token_ids=[1, 2],
                    sampling=SamplingParams(max_tokens=2))):
                pass
        assert DRAIN_REFUSAL in str(ei.value)

    _run(main())


# ---------------------------------------------------------------------------
# MigrationClient hardening (satellite): backoff, counters, drain refusal


def test_migration_backoff_is_jittered_exponential():
    mc = MigrationClient(None, retry_delay=0.1, max_retry_delay=2.0)
    for attempt, base in ((0, 0.1), (3, 0.8), (10, 2.0)):  # capped at max
        for _ in range(20):
            d = mc._backoff(attempt)
            assert base * 0.5 <= d <= base * 1.5, (attempt, d)
    # Jitter actually varies (not a fixed delay like the old 0.05 s).
    assert len({round(mc._backoff(1), 9) for _ in range(8)}) > 1


def test_migration_counter_reasons_and_drain_refusal_retry():
    """death → retry with backoff; a drain-refusal RpcError retries too;
    dynamo_migrations_total{reason} counts each rung."""

    class _Flaky:
        def __init__(self):
            self.calls = 0

        async def generate(self, request):
            self.calls += 1
            if self.calls == 1:
                raise ConnectionError("boom")
            if self.calls == 2:
                raise RpcError(f"refused: {DRAIN_REFUSAL}")
            yield TokenDelta(request_id=request.request_id,
                             token_ids=[7, 8], finished=True,
                             finish_reason=None)

    async def main():
        registry = MetricsRegistry()
        inner = _Flaky()
        mc = MigrationClient(inner, migration_limit=3, retry_delay=0.001,
                             registry=registry)
        req = PreprocessedRequest(request_id="r", model="m",
                                  token_ids=[1, 2, 3],
                                  sampling=SamplingParams(max_tokens=4))
        out = []
        async for d in mc.generate(req):
            out.extend(d.token_ids)
        assert out == [7, 8]
        assert inner.calls == 3
        assert mc.migrations == 2
        counter = registry.counter("migrations_total")
        assert counter.value({"reason": "death"}) == 1
        assert counter.value({"reason": "drain_refused"}) == 1

    _run(main())


def test_migration_budget_exhausted_raises():
    class _AlwaysDead:
        async def generate(self, request):
            raise ConnectionError("dead fleet")
            yield  # pragma: no cover

    async def main():
        mc = MigrationClient(_AlwaysDead(), migration_limit=2,
                             retry_delay=0.001)
        with pytest.raises(ConnectionError):
            async for _ in mc.generate(PreprocessedRequest(
                    request_id="r", model="m", token_ids=[1],
                    sampling=SamplingParams(max_tokens=4))):
                pass

    _run(main())


# ---------------------------------------------------------------------------
# QoS: priority classes, burn-triggered preemption, demote-then-resume


def test_priority_annotation_parse():
    def req(**ann):
        return PreprocessedRequest(request_id="r", model="m",
                                   token_ids=[1],
                                   sampling=SamplingParams(),
                                   annotations=dict(**ann))

    assert priority_of(req()) == 1
    assert priority_of(req(priority="best_effort")) == 0
    assert priority_of(req(priority="interactive")) == 2
    assert priority_of(req(priority="0")) == 0
    assert priority_of(req(priority="9")) == 2       # clamped
    assert priority_of(req(priority="garbage")) == 1  # forgiving


def _pump(core, got, stop, max_steps=600):
    """Step `core`, accumulating token_ids per request into `got`, until
    stop() is true (checked after each step's deltas are folded in)."""
    for _ in range(max_steps):
        for d in core.step():
            got.setdefault(d.request_id, []).extend(d.token_ids)
        if stop():
            return
    raise AssertionError(f"condition never met; got {got}")


def _reference_run(prompt, sampling, priority=0):
    core = _core(host_blocks=32)
    core.add_request("be", list(prompt), sampling, priority=priority)
    got = {}
    _pump(core, got, lambda: not core._requests)
    return got["be"]


def test_qos_burn_preempts_best_effort_demotes_then_resumes():
    """SLO burn >= 1 sheds a running best-effort request: its sealed KV
    demotes to the host tier (not lost), the standard request takes the
    machine, and when the burn clears the best-effort stream resumes via
    tier onboard — final output byte-identical to undisturbed serving."""
    want = _reference_run(LONG_PROMPT, SamplingParams(max_tokens=12))

    core = _core(host_blocks=32)
    pressure = [0.0]
    core.scheduler.qos_pressure_fn = lambda: pressure[0]
    core.add_request("be", list(LONG_PROMPT), SamplingParams(max_tokens=12),
                     priority=0)
    got = {"be": [], "std": []}

    # Let the best-effort stream decode a few tokens (blocks seal).
    _pump(core, got, lambda: len(got["be"]) >= 6)

    # Burn ignites; a standard-class request arrives.
    pressure[0] = 2.0
    core.add_request("std", list(range(100, 120)),
                     SamplingParams(max_tokens=6), priority=1)
    _pump(core, got, lambda: len(got["std"]) >= 6)
    sched = core.scheduler
    assert sched.qos_preemptions >= 1
    assert core.qos_demoted_blocks >= 1          # demoted, not lost
    host = core.allocator.manager.host
    assert len(host.registry.by_hash) >= 1       # blocks live in G2
    # Held while burning: the best-effort request made no progress past
    # the shed point.
    be_frozen = len(got["be"])
    for _ in range(10):
        for d in core.step():
            got.setdefault(d.request_id, []).extend(d.token_ids)
    assert len(got["be"]) == be_frozen

    # Burn clears: resume = tier onboard (not re-prefill), stream
    # completes byte-identical.
    pressure[0] = 0.0
    onboarded_before = core.allocator.manager.onboarded_blocks
    _pump(core, got, lambda: not core._requests)
    assert got["be"] == want, (got["be"], want)
    assert core.allocator.manager.onboarded_blocks > onboarded_before
    assert len(got["std"]) == 6


def test_qos_capacity_preemption_prefers_lower_class():
    """A capacity-blocked standard request displaces the newest
    best-effort request instead of waiting behind it (no SLO monitor
    involved — pure priority preemption)."""
    core = _core(host_blocks=32, num_blocks=12)  # 11 usable pages
    core.add_request("be", list(range(1, 41)),   # 6 pages at admission
                     SamplingParams(max_tokens=16), priority=0)
    got = {"be": [], "std": []}
    _pump(core, got, lambda: len(got["be"]) >= 1)

    core.add_request("std", list(range(200, 248)),   # needs 7 pages
                     SamplingParams(max_tokens=4), priority=1)
    _pump(core, got, lambda: not core._requests)
    assert core.scheduler.qos_preemptions >= 1
    assert len(got["std"]) == 4                  # standard got through
    assert len(got["be"]) == 16                  # best-effort completed after


# ---------------------------------------------------------------------------
# e2e chaos: kill -9 under load, control-plane partition


_seq = [0]


def _spawn_mock_worker(tmp_path, cp_port: int, name: str,
                       speedup: float = 1.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    _seq[0] += 1
    log = open(tmp_path / f"chaos_worker_{_seq[0]}.log", "w+")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.worker",
         "--control-plane", f"127.0.0.1:{cp_port}",
         "--mocker", "--model-name", name,
         "--block-size", "8",
         "--speedup-ratio", str(speedup)],
        env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT, text=True)
    proc._logfile = log  # type: ignore[attr-defined]
    return proc


async def _wait_prefix(cp, prefix, n, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            found = await cp.get_prefix(prefix)
        except (ConnectionError, RuntimeError, OSError):
            found = {}   # control plane mid-restart: keep polling
        if len(found) >= n:
            return found
        await asyncio.sleep(0.2)
    raise TimeoutError(f"never saw {n} entries under {prefix}")


async def _stream_request(session, base, model, rid_tag, max_tokens,
                          on_token=None):
    """One streaming chat request; returns (content_chunks,
    finish_reason)."""
    tokens = 0
    finish = None
    async with session.post(f"{base}/v1/chat/completions", json={
            "model": model,
            "messages": [{"role": "user", "content": f"chaos {rid_tag}"}],
            "max_tokens": max_tokens, "stream": True}) as r:
        assert r.status == 200, await r.text()
        async for raw in r.content:
            line = raw.decode().strip()
            if not line.startswith("data:") or line == "data: [DONE]":
                continue
            chunk = json.loads(line[5:])
            choice = chunk["choices"][0]
            if choice.get("delta", {}).get("content"):
                tokens += 1
                if on_token is not None:
                    on_token(tokens)
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
    return tokens, finish


@pytest.mark.e2e
def test_kill9_under_load_zero_failed_requests(tmp_path):
    """kill -9 one of two loaded workers: every concurrent stream
    completes (zero failed requests per the outcome counter), and the
    episode is asserted from flight-recorder DUMP CONTENTS plus the
    reaped status_endpoints entry — not from logs."""
    from aiohttp import ClientSession

    from dynamo_tpu.llm.discovery import ModelWatcher
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.service import ModelManager
    from dynamo_tpu.runtime.control_plane_tcp import (
        ControlPlaneClient, ControlPlaneServer)
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from tools.dynamo_top import collect
    from tools.trace_merge import load_flight_dump

    workers = []
    rec = flight_recorder.configure(service="chaos-frontend", enabled=True)
    rec.reset()

    async def main():
        cp_server = ControlPlaneServer()
        cp_port = await cp_server.start()
        cp = ControlPlaneClient("127.0.0.1", cp_port)
        await cp.start()
        runtime = DistributedRuntime(cp)
        models = ModelManager()
        registry = MetricsRegistry()
        watcher = ModelWatcher(runtime, models, migration_limit=3,
                               registry=registry)
        await watcher.start()
        svc = HttpService(models, registry=registry)
        http_port = await svc.start()

        workers.append(_spawn_mock_worker(tmp_path, cp_port, "chaos-model"))
        workers.append(_spawn_mock_worker(tmp_path, cp_port, "chaos-model"))
        await _wait_prefix(cp, "models/chaos-model/", 2)
        await _wait_prefix(cp, "status_endpoints/", 2)
        await watcher.wait_for_model("chaos-model", timeout=10)

        base = f"http://127.0.0.1:{http_port}"
        killed = [False]
        killed_pid = workers[0].pid

        def maybe_kill(tokens_seen):
            # Early trigger: the widest mid-flight window for the other
            # streams under CI contention.
            if tokens_seen >= 3 and not killed[0]:
                killed[0] = True
                workers[0].send_signal(signal.SIGKILL)

        async with ClientSession() as s:
            results = await asyncio.gather(*[
                _stream_request(s, base, "chaos-model", i, 24,
                                on_token=(maybe_kill if i == 0 else None))
                for i in range(6)])
        assert killed[0]
        # Reap the OS zombie: signal-0 pid probing (the status-endpoint
        # reaper's liveness test) sees zombie children as alive.
        workers[0].wait()
        for tokens, finish in results:
            assert finish == "length", results
            assert tokens >= 12, results  # streams actually progressed

        # 1) Zero failed requests, machine-checked via the outcome
        # counter the SLO error-rate objective reads.
        outcomes = svc.request_metrics.outcomes
        assert outcomes.value({"status": "error"}) == 0
        assert outcomes.value({"status": "ok"}) >= 6
        # 2) The migration evidence is in the flight-recorder dump.
        dump_path = str(tmp_path / "chaos_dump.jsonl")
        assert rec.dump("chaos_test", path=dump_path,
                        min_interval_s=0.0) == dump_path
        events = load_flight_dump(dump_path)
        migrates = [e for e in events if e.get("kind") == "migrate"]
        assert migrates, f"no migrate events in dump: {events[:5]}"
        assert any(e.get("reason") == "death" for e in migrates)
        # 3) The frontend counted the migration hops by reason.
        assert registry.counter("migrations_total").value(
            {"reason": "death"}) >= 1
        assert 'dynamo_migrations_total{reason="death"}' \
            in registry.expose()
        # 4) The kill -9'd worker's stale status registration reaps
        # (its pid is provably dead on loopback).
        snap = {}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            snap = await collect(f"127.0.0.1:{cp_port}", timeout=2.0)
            if any(r.get("reaped") and r.get("pid") == killed_pid
                   for r in snap.get("processes", [])):
                break
            await asyncio.sleep(0.5)
        assert any(r.get("reaped") and r.get("pid") == killed_pid
                   for r in snap.get("processes", [])), snap

        await watcher.stop()
        await svc.stop()
        await runtime.shutdown()
        await cp.close()
        await cp_server.stop()

    try:
        _run(main())
    finally:
        rec.configure(enabled=False)
        rec.reset()
        for w in workers:
            if w.poll() is None:
                w.kill()


@pytest.mark.e2e
def test_control_plane_partition_recovery(tmp_path):
    """Partition the control plane mid-stream (kill -9 + restart on the
    same port/store): the in-flight stream — worker↔frontend RPC is a
    direct connection — completes; after recovery the worker's lease
    re-registers and fresh requests serve.  Zero failed requests."""
    from aiohttp import ClientSession

    from dynamo_tpu.llm.discovery import ModelWatcher
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.service import ModelManager
    from dynamo_tpu.runtime.control_plane_tcp import ControlPlaneClient

    store = str(tmp_path / "cp.json")
    procs = []

    def start_cp(port):
        log = open(tmp_path / f"cp_{len(procs)}.log", "w+")
        p = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.control_plane_service",
             "--port", str(port), "--store", f"file:{store}"],
            env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO,
            stdout=log, stderr=subprocess.STDOUT, text=True)
        p._logfile = log  # type: ignore[attr-defined]
        procs.append(p)
        return p

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    cp_port = s.getsockname()[1]
    s.close()

    async def main():
        from dynamo_tpu.runtime.distributed import DistributedRuntime

        cp_proc = start_cp(cp_port)
        cp = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                cp = ControlPlaneClient("127.0.0.1", cp_port)
                await cp.start()
                break
            except OSError:
                await asyncio.sleep(0.3)
        assert cp is not None
        runtime = DistributedRuntime(cp)
        models = ModelManager()
        watcher = ModelWatcher(runtime, models, migration_limit=3)
        await watcher.start()
        svc = HttpService(models)
        http_port = await svc.start()

        procs.append(_spawn_mock_worker(tmp_path, cp_port, "part-model"))
        await _wait_prefix(cp, "models/part-model/", 1)
        await watcher.wait_for_model("part-model", timeout=10)
        base = f"http://127.0.0.1:{http_port}"

        partitioned = [False]

        def partition(tokens_seen):
            if tokens_seen == 4 and not partitioned[0]:
                partitioned[0] = True
                cp_proc.send_signal(signal.SIGKILL)

        async with ClientSession() as s:
            tokens, finish = await _stream_request(
                s, base, "part-model", "p0", 30, on_token=partition)
            assert partitioned[0]
            # The stream rode out the partition on its direct RPC.
            assert finish == "length" and tokens >= 15

            cp_proc.wait()
            start_cp(cp_port)
            # Worker lease recovery re-registers the same instance; the
            # frontend watch replays it.  A fresh request then serves.
            await _wait_prefix(cp, "models/part-model/", 1, timeout=90)
            tokens2, finish2 = await _stream_request(
                s, base, "part-model", "p1", 6)
            assert finish2 == "length" and tokens2 >= 3

        outcomes = svc.request_metrics.outcomes
        assert outcomes.value({"status": "error"}) == 0
        await watcher.stop()
        await svc.stop()
        await runtime.shutdown()
        await cp.close()

    try:
        _run(main(), timeout=240)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            log = getattr(p, "_logfile", None)
            if log:
                log.flush()


@pytest.mark.e2e
def test_worker_sigterm_drain_hands_off_stream(tmp_path):
    """SIGTERM a loaded worker (mocker, so the handoff carries no KV
    hint): the in-flight stream migrates to the survivor with reason
    "drain" — not "death" — the drained worker exits 0 on its own, and a
    control-plane drain command drains the second worker the same way."""
    from aiohttp import ClientSession

    from dynamo_tpu.llm.discovery import ModelWatcher
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.service import ModelManager
    from dynamo_tpu.runtime.control_plane_tcp import (
        ControlPlaneClient, ControlPlaneServer)
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    workers = []

    async def main():
        cp_server = ControlPlaneServer()
        cp_port = await cp_server.start()
        cp = ControlPlaneClient("127.0.0.1", cp_port)
        await cp.start()
        runtime = DistributedRuntime(cp)
        models = ModelManager()
        registry = MetricsRegistry()
        watcher = ModelWatcher(runtime, models, migration_limit=3,
                               registry=registry)
        await watcher.start()
        svc = HttpService(models, registry=registry)
        http_port = await svc.start()

        workers.append(_spawn_mock_worker(tmp_path, cp_port, "drain-model"))
        workers.append(_spawn_mock_worker(tmp_path, cp_port, "drain-model"))
        await _wait_prefix(cp, "models/drain-model/", 2)
        # A worker registers its model before it installs its SIGTERM
        # handler; until then the signal's default action kills it and
        # the streams migrate with reason "death".  Its status endpoint
        # is put by a task that first runs after the handler is in.
        await _wait_prefix(cp, "status_endpoints/", 2)
        await watcher.wait_for_model("drain-model", timeout=10)
        base = f"http://127.0.0.1:{http_port}"

        terminated = [False]

        def sigterm_one(tokens_seen):
            # Early trigger: the widest mid-flight window for the other
            # streams under CI contention.
            if tokens_seen >= 2 and not terminated[0]:
                terminated[0] = True
                workers[0].send_signal(signal.SIGTERM)

        async with ClientSession() as s:
            # Worker 0 drains mid-load; with worker 1 surviving, every
            # stream must complete (the drain handoff or — racing the
            # drain window — a retryable refusal re-routes them).
            results = await asyncio.gather(*[
                _stream_request(s, base, "drain-model", i, 24,
                                on_token=(sigterm_one if i == 0 else None))
                for i in range(4)])
        assert terminated[0]
        for tokens, finish in results:
            assert finish == "length", results       # zero failed requests
        drains = registry.counter("migrations_total").value(
            {"reason": "drain"})
        refusals = registry.counter("migrations_total").value(
            {"reason": "drain_refused"})
        assert drains + refusals >= 1, registry.expose()
        # The drained worker exits on its own, cleanly (rc 0), inside
        # the drain budget — no SIGKILL involved.
        assert await asyncio.to_thread(workers[0].wait, 60) == 0

        await watcher.stop()
        await svc.stop()
        await runtime.shutdown()
        await cp.close()
        await cp_server.stop()

    try:
        _run(main(), timeout=240)
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()


@pytest.mark.e2e
def test_control_plane_drain_command(tmp_path):
    """`cp.put(drain/<pid>)` drains a worker without any signal — the
    container/remote-host path: it leaves routing and exits 0."""
    from dynamo_tpu.llm.drain import drain_key_pid
    from dynamo_tpu.runtime.control_plane_tcp import (
        ControlPlaneClient, ControlPlaneServer)

    workers = []

    async def main():
        cp_server = ControlPlaneServer()
        cp_port = await cp_server.start()
        cp = ControlPlaneClient("127.0.0.1", cp_port)
        await cp.start()
        workers.append(_spawn_mock_worker(tmp_path, cp_port, "cmd-model"))
        await _wait_prefix(cp, "models/cmd-model/", 1)
        # The worker starts watching drain/ after it registers its
        # model, and a put before that is lost: its status endpoint is
        # put by the task that runs just before the watch request.
        await _wait_prefix(cp, "status_endpoints/", 1)

        await cp.put(drain_key_pid(workers[0].pid), {"reason": "test"})
        rc = await asyncio.to_thread(workers[0].wait, 60)
        assert rc == 0
        # The instance record left with the worker (lease revoked on
        # drain, not just expiry).
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if not await cp.get_prefix("models/cmd-model/"):
                break
            await asyncio.sleep(0.2)
        assert not await cp.get_prefix("models/cmd-model/")
        await cp.close()
        await cp_server.stop()

    try:
        _run(main(), timeout=180)
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()


# ---------------------------------------------------------------------------
# Planner drain accounting (satellite): clean drain vs force-kill


def test_connector_counts_force_kill_distinct_from_clean_drain(tmp_path):
    from dynamo_tpu.planner.connector import LocalConnector
    from dynamo_tpu.planner.core import planner_metrics_text

    async def main():
        conn = LocalConnector("127.0.0.1:1", drain_timeout_s=1.0,
                              log_dir=str(tmp_path))
        # A worker that honors SIGTERM → clean drain.
        good = subprocess.Popen([sys.executable, "-c",
                                 "import time; time.sleep(60)"])
        # A worker that ignores SIGTERM → drain timeout → force-kill.
        # Handshake on stdout so SIGTERM can't race the handler install.
        bad = subprocess.Popen([sys.executable, "-u", "-c",
                                "import signal, time;"
                                "signal.signal(signal.SIGTERM,"
                                " signal.SIG_IGN);"
                                "print('armed', flush=True);"
                                "time.sleep(60)"],
                               stdout=subprocess.PIPE, text=True)
        assert bad.stdout.readline().strip() == "armed"
        conn._procs = [good, bad]
        await conn.remove_worker()   # pops `bad` (newest) → force-kill
        await conn.remove_worker()   # pops `good` → clean drain
        assert conn.force_kills == 1
        assert conn.clean_drains == 1
        text = planner_metrics_text(object(), conn)
        assert 'dynamo_planner_drains_total{outcome="clean"} 1' in text
        assert 'dynamo_planner_drains_total{outcome="force_kill"} 1' in text

    _run(main())


def test_migrate_annotation_cleared_on_death_retry():
    """A death-retry must not chase the previous hop's migrate hint —
    the re-issued request drops MIGRATE_ANNOTATION unless a fresh
    migrate delta carried one."""

    class _DieOnce:
        def __init__(self):
            self.calls = 0
            self.seen = []

        async def generate(self, request):
            self.calls += 1
            self.seen.append(dict(request.annotations))
            if self.calls == 1:
                yield TokenDelta(request_id=request.request_id,
                                 token_ids=[5], finished=False)
                raise ConnectionError("died mid-stream")
            yield TokenDelta(request_id=request.request_id,
                             token_ids=[6], finished=True)

    async def main():
        inner = _DieOnce()
        mc = MigrationClient(inner, retry_delay=0.001)
        req = PreprocessedRequest(
            request_id="r", model="m", token_ids=[1, 2],
            sampling=SamplingParams(max_tokens=4),
            annotations={MIGRATE_ANNOTATION:
                         '{"address": "stale:1", "covered_tokens": 8}'})
        out = []
        async for d in mc.generate(req):
            out.extend(d.token_ids)
        assert out == [5, 6]
        assert MIGRATE_ANNOTATION in inner.seen[0]       # first attempt
        assert MIGRATE_ANNOTATION not in inner.seen[1]   # cleared on retry
        # Budget + seed bookkeeping on the re-issue.
        assert inner.calls == 2

    _run(main())
