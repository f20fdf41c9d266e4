"""Flight recorder + stall watchdog (ISSUE 14): ring semantics, dump
triggers, the injected-engine-stall detection path, the recorder-on
steady-window zero-overhead pin, the /debug/flightrecorder surfaces,
and trace_merge's --flight instant-event merging.

Engine-backed tests share ONE tiny geometry (test_decode_window's steady
config) so every EngineCore build hits the persistent
XLA compile cache — tier-1 budget discipline.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from dynamo_tpu.runtime import flight_recorder
from dynamo_tpu.runtime.flight_recorder import FlightRecorder, StallWatchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def recorder(tmp_path):
    """The module singleton, enabled into a tmp dump dir and restored to
    the disabled default afterwards (other tests pin recorder-off
    behavior)."""
    rec = flight_recorder.get_recorder()
    rec.reset()
    rec.configure(enabled=True, ring_size=512, dump_dir=str(tmp_path),
                  service="test")
    yield rec
    rec.reset()
    rec.configure(enabled=False, service="dynamo",
                  ring_size=flight_recorder.DEFAULT_RING)
    rec.dump_dir = None


def _tiny_engine(**kw):
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg

    defaults = dict(
        model=mcfg.get_config("tiny-test"), num_blocks=128,
        enable_prefix_cache=False, decode_window=2,
        window_pipeline_depth=2,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=32,
            max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 128)))
    defaults.update(kw)
    return EngineCore(EngineConfig(**defaults))


# -- ring semantics ----------------------------------------------------------


def test_ring_records_wraps_and_orders(recorder):
    small = FlightRecorder(enabled=True, ring_size=8)
    for i in range(13):
        small.record("k", i=i)
    ev = small.events()
    assert len(ev) == 8
    assert [e["i"] for e in ev] == list(range(5, 13))   # oldest dropped
    assert small.events_written == 13
    assert [e["i"] for e in small.events(3)] == [10, 11, 12]
    # n <= 0 = envelope only, never the whole ring by slice degeneracy.
    assert small.events(0) == [] and small.events(-3) == []
    # Every event carries the uniform envelope.
    assert all({"seq", "ts", "kind"} <= set(e) for e in ev)


def test_disabled_recorder_is_a_noop_but_record_always_is_not():
    rec = FlightRecorder(enabled=False, ring_size=8)
    rec.record("never", x=1)
    assert rec.events() == [] and rec.events_written == 0
    rec.record_always("stall", age_s=1.0)
    assert [e["kind"] for e in rec.events()] == ["stall"]


def test_heartbeat_age(recorder):
    rec = FlightRecorder()
    assert rec.last_step_age_s() is None     # never stepped ≠ stalled
    rec.beat()
    age = rec.last_step_age_s()
    assert age is not None and age < 1.0


def test_dump_writes_header_and_events_and_throttles(recorder, tmp_path):
    recorder.record("admit", rid="r1", prompt=64)
    recorder.record("window", bucket=8, width=16, lag=1)
    path = recorder.dump("unit_test", min_interval_s=0.0)
    assert path and os.path.dirname(path) == str(tmp_path)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["flight_dump"] is True
    assert lines[0]["reason"] == "unit_test"
    assert lines[0]["pid"] == os.getpid()
    assert lines[0]["events"] == 2
    assert [l["kind"] for l in lines[1:]] == ["admit", "window"]
    assert lines[1]["rid"] == "r1"
    # Per-reason throttle: an immediate re-dump of the same reason is
    # suppressed; a different reason is not.
    assert recorder.dump("unit_test", min_interval_s=60.0) is None
    assert recorder.dump("other_reason", min_interval_s=60.0) is not None
    assert recorder.dumps_written == 2


def test_debug_payload_shape(recorder):
    recorder.record("kv_plane", plane="device", reason="eager")
    p = recorder.debug_payload(16)
    assert p["enabled"] is True
    assert p["service"] == "test"
    assert p["pid"] == os.getpid()
    assert p["stalls"] == 0
    assert p["events"][-1]["kind"] == "kv_plane"
    assert p["events_written"] == 1


# -- stall watchdog ----------------------------------------------------------


def test_watchdog_check_once_is_deterministic(recorder):
    """Stall declared iff heartbeat is old AND work is pending; one
    episode counts once; heartbeat resume re-arms."""
    pending = {"v": True}
    wd = StallWatchdog(recorder, lambda: pending["v"], stall_s=5.0)
    # Never stepped: starting, not stalled.
    assert wd.check_once(now=time.monotonic() + 100) is False
    recorder.beat()
    t0 = recorder.last_beat
    # Fresh heartbeat: fine.
    assert wd.check_once(now=t0 + 1.0) is False
    # Old heartbeat + pending work: stall (counted, dumped, recorded).
    assert wd.check_once(now=t0 + 6.0) is True
    assert wd.stalled and recorder.stalls == 1
    assert recorder.last_dump_path is not None
    assert any(e["kind"] == "stall" for e in recorder.events())
    # Same episode: no double count.
    assert wd.check_once(now=t0 + 60.0) is False
    assert recorder.stalls == 1
    # Heartbeat resumes: re-armed; a NEW wedge counts again.
    recorder.beat()
    assert wd.check_once(now=recorder.last_beat + 1.0) is False
    assert not wd.stalled
    assert wd.check_once(now=recorder.last_beat + 6.0) is True
    assert recorder.stalls == 2
    # Old heartbeat but NO pending work: an idle engine is at rest.
    pending["v"] = False
    recorder.beat()
    assert wd.check_once(now=recorder.last_beat + 60.0) is False
    assert recorder.stalls == 2


def test_watchdog_compile_grace_widens_threshold(recorder):
    """A first-seen-shape compile stamped at/after the last heartbeat
    widens the stall threshold to compile_grace_s (a 30 s XLA compile
    on a cold start is not a wedge); a wedge WITHOUT a preceding
    compile still pages at stall_s, and a wedge DURING a compile pages
    at the grace."""
    wd = StallWatchdog(recorder, lambda: True, stall_s=5.0,
                       compile_grace_s=60.0)
    recorder.last_beat = 100.0
    recorder.last_compile = 100.5       # current step is compiling
    assert wd.check_once(now=110.0) is False   # past stall_s: grace holds
    assert wd.check_once(now=161.0) is True    # past the grace: a wedge
    # Heartbeat advanced past the compile stamp: back to stall_s.
    recorder.last_beat = 200.0
    assert wd.check_once(now=201.0) is False   # recovered
    assert not wd.stalled
    assert wd.check_once(now=206.0) is True    # plain wedge at stall_s
    assert recorder.stalls == 2


def test_watchdog_pending_fn_exception_reads_as_idle(recorder):
    def boom():
        raise RuntimeError("racing teardown")

    wd = StallWatchdog(recorder, boom, stall_s=1.0)
    recorder.beat()
    assert wd.check_once(now=recorder.last_beat + 10.0) is False
    assert recorder.stalls == 0


def test_engine_stall_detected_by_live_watchdog(recorder, tmp_path):
    """THE acceptance path: a real engine with pending work stops
    stepping; the watchdog THREAD declares the stall within its window,
    increments the counter, and dumps — then the engine resumes and the
    watchdog re-arms."""
    from dynamo_tpu.engine.sampling import SamplingParams

    core = _tiny_engine()
    core.add_request("a", list(range(1, 71)),
                     SamplingParams(max_tokens=64))
    for _ in range(6):
        core.step()
    assert core.has_work                      # decode work in flight
    # compile_grace_s == stall_s: the last executed step may have
    # stamped a compile (new shape), and this test injects a WEDGE, not
    # a long compile — neutralize the grace so the window is exact.
    wd = StallWatchdog(recorder, lambda: core.has_work, stall_s=0.15,
                       interval_s=0.05, compile_grace_s=0.15)
    wd.start()
    try:
        # Engine thread "wedges": nobody calls step().
        deadline = time.monotonic() + 5.0
        while recorder.stalls == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert recorder.stalls == 1, "watchdog never declared the stall"
        assert wd.stalled
        dump = recorder.last_dump_path
        assert dump and os.path.exists(dump)
        rows = [json.loads(l) for l in open(dump)]
        assert rows[0]["reason"] == "stall"
        assert any(r.get("kind") == "stall" for r in rows[1:])
        # The ring carries the pre-stall story: the engine's own
        # dispatch events precede the stall marker.
        kinds = [r.get("kind") for r in rows[1:]]
        assert "window" in kinds or "prefill" in kinds
        # Engine recovers: stepping resumes, watchdog re-arms.
        for _ in range(3):
            core.step()
        deadline = time.monotonic() + 5.0
        while wd.stalled and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not wd.stalled
        assert recorder.stalls == 1           # no new episode
    finally:
        wd.stop()


# -- engine integration ------------------------------------------------------


def test_engine_records_admissions_dispatches_recompiles(recorder):
    from dynamo_tpu.engine.sampling import SamplingParams

    core = _tiny_engine()
    core.add_request("a", list(range(1, 71)), SamplingParams(max_tokens=24))
    for _ in range(40):
        core.step()
        if not core._requests:
            break
    kinds = {e["kind"] for e in recorder.events()}
    assert {"admit", "prefill", "window", "recompile"} <= kinds
    admit = next(e for e in recorder.events() if e["kind"] == "admit")
    assert admit["rid"] == "a" and admit["prompt"] == 70
    rec_ev = next(e for e in recorder.events() if e["kind"] == "recompile")
    assert rec_ev["tag"]                       # program named
    # Heartbeat stamped by step() itself.
    assert recorder.last_step_age_s() is not None


def test_steady_window_recorder_on_is_byte_identical():
    """The overhead pin (ISSUE 14 acceptance): 20 steady window steps
    with the recorder ENABLED produce the exact same EngineStepCounters
    deltas as recorder-off — 0 extra host syncs, 0 extra dispatches, 0
    recompiles — and stay inside the ring-write budget of one write per
    window dispatch (+1 periodic counters breadcrumb)."""
    from dynamo_tpu.engine.sampling import SamplingParams

    rec = flight_recorder.get_recorder()

    def steady_run(enabled):
        rec.reset()
        rec.enabled = enabled
        core = _tiny_engine()
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):   # prefill + window warmup
            core.step()
        base = core.counters.snapshot()
        writes0 = rec.events_written
        for _ in range(20):
            core.step()
        return core.counters.delta(base), rec.events_written - writes0

    try:
        d_off, w_off = steady_run(False)
        d_on, w_on = steady_run(True)
    finally:
        rec.reset()
        rec.enabled = False
    assert w_off == 0
    assert d_on == d_off, (d_on, d_off)        # byte-identical counters
    assert d_on["host_syncs"] == d_off["host_syncs"]
    assert d_on["window_dispatches"] == 20
    assert 0 < w_on <= d_on["window_dispatches"] + 1, w_on


# -- trigger integrations ----------------------------------------------------


def test_slo_page_transition_records_and_dumps(recorder):
    from dynamo_tpu.runtime.slo import PAGE, SloMonitor, SloObjective

    state = {"total": 0.0, "bad": 0.0}
    mon = SloMonitor(
        [(SloObjective("error_rate", objective=0.99),
          lambda: (state["total"], state["bad"]))],
        clock=lambda: 0.0)
    mon.tick(now=0.0)                      # baseline sample, state OK
    state.update(total=100.0, bad=100.0)   # everything failing
    payload = mon.tick(now=10.0)
    assert payload["state"] == PAGE
    ev = [e for e in recorder.events() if e["kind"] == "slo_state"]
    assert ev and ev[-1]["prev"] == "OK" and ev[-1]["state"] == PAGE
    # The PAGE dump rides a short-lived thread (the tick may run on the
    # serving event loop): poll for it.
    deadline = time.monotonic() + 5.0
    while recorder.dumps_written == 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert recorder.last_dump_path is not None
    header = json.loads(open(recorder.last_dump_path).readline())
    assert header["reason"] == "slo_page"
    # Recovery transition records too (no dump needed for PAGE→OK).
    state.update(total=100000.0, bad=100.0)
    dumps_before = recorder.dumps_written
    mon.tick(now=20.0)
    time.sleep(0.1)
    ev = [e for e in recorder.events() if e["kind"] == "slo_state"]
    assert ev[-1]["prev"] == PAGE
    assert recorder.dumps_written == dumps_before


def test_scheduler_preempt_and_kv_plane_breadcrumbs(recorder):
    from dynamo_tpu.llm.block_manager import device_transfer

    device_transfer.note_plane("host", "no_plane")
    ev = recorder.events()
    assert ev[-1]["kind"] == "kv_plane"
    assert ev[-1]["plane"] == "host" and ev[-1]["reason"] == "no_plane"


# -- surfaces ----------------------------------------------------------------


def test_debug_flightrecorder_routes(recorder):
    """Both process surfaces serve the SAME payload shape: the worker's
    StatusServer and the frontend's HttpService."""
    import aiohttp

    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.service import ModelManager
    from dynamo_tpu.runtime.status import StatusServer

    recorder.record("window", bucket=4, width=8, lag=1)

    async def main():
        status = StatusServer()
        sport = await status.start()
        svc = HttpService(ModelManager())
        fport = await svc.start()
        try:
            async with aiohttp.ClientSession() as s:
                for port in (sport, fport):
                    async with s.get(
                            "http://127.0.0.1:%d/debug/flightrecorder"
                            "?n=16" % port) as r:
                        assert r.status == 200
                        body = await r.json()
                    assert body["enabled"] is True
                    assert body["events"][-1]["kind"] == "window"
                    assert body["stalls"] == 0
                async with s.get(
                        f"http://127.0.0.1:{sport}/debug/flightrecorder"
                        "?n=bogus") as r:
                    assert r.status == 400
        finally:
            await svc.stop()
            await status.stop()

    asyncio.run(asyncio.wait_for(main(), 60))


def test_trace_merge_flight_events(recorder, tmp_path):
    """--flight merges recorder dumps as instant markers on the owning
    process's EXISTING track (shared service name), deduped across
    overlapping dumps."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_merge

    payload = {"service": "worker-backend", "enabled": True, "traces": [{
        "trace_id": "t1", "service": "worker-backend", "spans": [
            {"name": "engine.prefill", "trace_id": "t1", "span_id": "s1",
             "parent_id": None, "service": "worker-backend",
             "ts": 1000.0, "dur": 0.5, "attrs": {"rid": "r1"}}]}]}
    recorder.configure(service="worker-backend")
    recorder.record("window", bucket=8, width=16, lag=1)
    recorder.record("stall", age_s=12.0)
    dump = recorder.dump("stall", min_interval_s=0.0)

    merged = trace_merge.merge_payloads([payload])
    # Load the SAME dump twice: (service, seq) dedupe must collapse it.
    events = (trace_merge.load_flight_dump(dump)
              + trace_merge.load_flight_dump(dump))
    added = trace_merge.merge_flight_events(merged, events)
    assert added == 2
    inst = [e for e in merged["traceEvents"] if e["ph"] == "i"]
    assert {e["name"] for e in inst} == {"fr.window", "fr.stall"}
    span_pid = next(e["pid"] for e in merged["traceEvents"]
                    if e["ph"] == "X")
    # Instant markers ride the owning process's existing track.
    assert all(e["pid"] == span_pid for e in inst)
    assert all(e["cat"] == "flight" for e in inst)
    w = next(e for e in inst if e["name"] == "fr.window")
    assert w["args"]["bucket"] == 8


def test_trace_merge_flight_unknown_service_gets_new_track(recorder,
                                                           tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_merge

    recorder.configure(service="worker-prefill")
    recorder.record("admit", rid="r9", prompt=8, cached=0, new_pages=1)
    dump = recorder.dump("sigusr2", min_interval_s=0.0)
    merged = trace_merge.merge_payloads([{"service": "frontend",
                                          "traces": []}])
    added = trace_merge.merge_flight_events(
        merged, trace_merge.load_flight_dump(dump))
    assert added == 1
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "worker-prefill" in names


# -- live worker (slow) ------------------------------------------------------


@pytest.mark.slow
def test_sigusr2_dumps_live_worker(tmp_path):
    """kill -USR2 a REAL worker process → flight dump appears in
    --flight-dump-dir with the sigusr2 reason, parseable JSONL; the
    worker's /metrics carries the AGE/STL series and its StatusServer
    serves /debug/flightrecorder."""
    import re

    import aiohttp

    from dynamo_tpu.runtime.control_plane_tcp import ControlPlaneServer

    async def main():
        srv = ControlPlaneServer()
        cp_port = await srv.start()
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        log = open(tmp_path / "worker.log", "w+")
        proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.worker",
             "--control-plane", f"127.0.0.1:{cp_port}",
             "--mocker", "--model-name", "fr-test", "--block-size", "8",
             "--flight-dump-dir", str(tmp_path)],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
        try:
            # Wait for the worker to finish starting (instance line).
            deadline = time.monotonic() + 60
            text = ""
            while time.monotonic() < deadline:
                log.flush()
                log.seek(0)
                text = log.read()
                if "worker instance" in text:
                    break
                await asyncio.sleep(0.2)
            else:
                raise AssertionError("worker never started: "
                                     + open(tmp_path / "worker.log").read())
            m = re.search(r"worker status server on :(\d+)", text)
            assert m, text
            sport = int(m.group(1))
            async with aiohttp.ClientSession() as s:
                async with s.get(
                        f"http://127.0.0.1:{sport}/metrics") as r:
                    assert r.status == 200
                    metrics = await r.text()
                # The stall series exist on every worker (the mocker
                # has no heartbeat, so only the counter/flag lines).
                assert "dynamo_engine_stalls_total 0" in metrics
                assert "dynamo_engine_stalled 0" in metrics
                async with s.get(f"http://127.0.0.1:{sport}"
                                 "/debug/flightrecorder?n=8") as r:
                    assert r.status == 200
                    fr = await r.json()
                assert fr["enabled"] is True
                assert fr["pid"] == proc.pid
                assert fr["service"] == "worker-backend"
            dump_path = tmp_path / f"flight_worker-backend_{proc.pid}.jsonl"
            proc.send_signal(signal.SIGUSR2)
            deadline = time.monotonic() + 30
            header = None
            while time.monotonic() < deadline:
                if dump_path.exists():
                    rows = [json.loads(l)
                            for l in open(dump_path) if l.strip()]
                    headers = [r for r in rows if r.get("flight_dump")]
                    if any(r["reason"] == "sigusr2" for r in headers):
                        header = next(r for r in headers
                                      if r["reason"] == "sigusr2")
                        break
                await asyncio.sleep(0.2)
            assert header is not None, "no sigusr2 dump appeared"
            assert header["pid"] == proc.pid
            assert header["service"] == "worker-backend"
        finally:
            # SIGKILL, not SIGTERM: the mocker worker's graceful drain
            # can hang when its control plane goes away (pre-existing —
            # the other e2e tests kill -9 too), and this test's subject
            # is the SIGUSR2 dump, which already happened.
            proc.kill()
            proc.wait(timeout=20)
            log.close()
            await srv.stop()

    asyncio.run(asyncio.wait_for(main(), 150))
