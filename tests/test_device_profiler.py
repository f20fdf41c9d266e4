"""Device-truth profiling plane (ISSUE 20): XLA cost-analysis harvest
riding first-seen dispatch shapes, the modeled-vs-measured drift
auditor's band/PAGE state machine, the steady-window zero-overhead pin,
the /debug/deviceprofile surfaces, on-demand bounded capture, and
trace_merge's --device lane merging.

Engine-backed tests share test_decode_window's tiny geometry (and test_packed_prefill's GEOM for the prewarm pin) so every
EngineCore build hits the persistent XLA compile cache — tier-1 budget
discipline.
"""

import asyncio
import gzip
import json
import os
import re
import subprocess
import sys
import time

import pytest

from dynamo_tpu.runtime import device_profiler, flight_recorder
from dynamo_tpu.runtime.device_profiler import (
    DriftAuditor,
    PAGE_STRIKES,
    ProgramCostRegistry,
    profile_key_instance,
    profile_key_pid,
    program_label,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def profiler(tmp_path):
    """The module singleton, enabled into a tmp capture dir and restored
    to the disabled default afterwards (other tests pin plane-off
    behavior)."""
    prof = device_profiler.get_profiler()
    prof.reset()
    prof.configure(enabled=True, service="test",
                   dump_dir=str(tmp_path))
    yield prof
    prof.reset()
    prof.configure(enabled=False, service="dynamo",
                   max_capture_ms=device_profiler.DEFAULT_MAX_CAPTURE_MS,
                   band_hi=device_profiler.DEFAULT_BAND_HI,
                   band_lo=device_profiler.DEFAULT_BAND_LO)
    prof.dump_dir = None


@pytest.fixture()
def recorder(tmp_path):
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


# -- registry ----------------------------------------------------------------


def test_program_label_matches_dispatch_identity():
    assert program_label("prefill", (1, 128, 16, False, False)) \
        == "prefill:1,128,16,False,False"
    assert program_label("window", (True, 1, 16)) == "window:True,1,16"


def test_registry_record_tags_and_topk():
    reg = ProgramCostRegistry()
    reg.record("window:True,1,16", flops=100.0, bytes_accessed=1000.0)
    reg.record("decode1g:1,16", flops=50.0, bytes_accessed=600.0,
               optimal_s=2e-6)
    reg.record("prefill:1,128,16,False,False", flops=9000.0,
               bytes_accessed=8000.0)
    assert reg.size() == 3
    assert reg.get("decode1g:1,16")["optimal_s"] == 2e-6
    assert reg.get("window:True,1,16")["optimal_s"] is None
    # tag_values keys on the label prefix before the first ':'.
    assert reg.tag_values("bytes_accessed", "window") == [1000.0]
    assert sorted(reg.tag_values("bytes_accessed",
                                 "decode1", "decode1g")) == [600.0]
    assert reg.mean_for_tags("bytes_accessed", "nope") is None
    top = reg.top_by("bytes_accessed", 2)
    assert [label for label, _ in top] == [
        "prefill:1,128,16,False,False", "window:True,1,16"]
    reg.reset()
    assert reg.size() == 0


def test_profile_command_keys():
    assert profile_key_pid(123) == "profile/123"
    assert profile_key_instance(7) == "profile/instance/7"


# -- leg 1: harvest at the dispatch sites ------------------------------------


def test_harvest_lands_real_engine_programs(profiler):
    """Serving a request with the plane enabled harvests XLA cost
    analysis for every first-seen dispatch shape — prefill and the
    decode window at minimum — with real nonzero flops/bytes, and the
    registry identity matches note_dispatch's (tag, sig) key."""
    from dynamo_tpu.engine.sampling import SamplingParams

    core = _tiny_engine()
    core.add_request("a", list(range(1, 71)), SamplingParams(max_tokens=24))
    for _ in range(40):
        core.step()
        if not core._requests:
            break
    assert profiler.harvest_failures == 0
    tags = {label.split(":", 1)[0] for label, _ in profiler.registry.items()}
    assert {"prefill", "window"} <= tags
    for label, costs in profiler.registry.items():
        assert costs["flops"] > 0, label
        assert costs["bytes_accessed"] > 0, label
    # Every registry label corresponds to a seen dispatch shape.
    seen = {program_label(k[0], tuple(k[1:]))
            for k in core.counters._seen_shapes}
    assert {label for label, _ in profiler.registry.items()} <= seen


def test_prewarm_shapes_land_in_registry(profiler):
    """The --prewarm-prefill bugfix pin: prewarmed packed shapes reach
    the cost registry through the same first-seen path as serving
    dispatches — prewarming must not create a permanently-dark program
    set (and the harvest must run BEFORE the donating dispatch)."""
    from dynamo_tpu.engine.scheduler import SchedulerConfig

    core = _tiny_engine(
        packed_prefill=True, decode_window=0, window_pipeline_depth=0,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=16,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(8, 16, 32)))
    shapes = core.packed_prefill_shape_set()
    assert core.prewarm_prefill() == len(shapes)
    want = {program_label("prefill_packed", s) for s in shapes}
    got = {label for label, _ in profiler.registry.items()
           if label.startswith("prefill_packed:")}
    assert got == want
    assert profiler.harvest_failures == 0


def test_harvest_disabled_and_unlowerable_are_noops(profiler):
    profiler.enabled = False
    assert profiler.harvest("t", (1,), lambda x: x, (1,)) is False
    profiler.enabled = True
    # Plain callables without .lower (sharded/pp step makers) degrade
    # silently — no failure counted, serving never at risk.
    assert profiler.harvest("t", (1,), lambda x: x, (1,)) is False
    assert profiler.harvest_failures == 0
    assert profiler.registry.size() == 0


# -- leg 2: drift auditor ----------------------------------------------------


def test_drift_auditor_band_and_page_state_machine(recorder):
    """Out-of-band observations must persist for PAGE_STRIKES
    consecutive scrapes before paging (one mid-warmup blip must not
    dump the ring); the PAGE records a drift_page event + async ring
    dump; returning in band records drift_ok and re-arms."""
    aud = DriftAuditor(band_hi=1.25)
    # In-band: ok, no strikes.
    assert aud.observe("kv_decode", 0.5, 1.0) == 0.5
    assert aud.states()["kv_decode"] == {
        "ratio": 0.5, "state": "ok", "strikes": 0}
    # Two strikes, then a recovery: the episode resets, never pages.
    assert aud.observe("kv_decode", 2.0, 1.0) == 2.0
    assert aud.observe("kv_decode", 2.0, 1.0) == 2.0
    assert aud.states()["kv_decode"]["strikes"] == 2
    assert aud.observe("kv_decode", 1.0, 1.0) == 1.0
    assert aud.states()["kv_decode"]["strikes"] == 0
    assert not aud.paged()
    # PAGE_STRIKES consecutive out-of-band: PAGE once, with evidence.
    for _ in range(PAGE_STRIKES):
        aud.observe("kv_decode", 3.0, 1.0)
    assert aud.paged()
    ev = [e for e in recorder.events() if e["kind"] == "drift_page"]
    assert len(ev) == 1
    assert ev[0]["series"] == "kv_decode" and ev[0]["ratio"] == 3.0
    # The dump rides a short-lived thread: poll for it.
    deadline = time.monotonic() + 5.0
    while recorder.dumps_written == 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert recorder.last_dump_path is not None
    header = json.loads(open(recorder.last_dump_path).readline())
    assert header["reason"] == "drift_page"
    # Still out of band: no re-page spam.
    aud.observe("kv_decode", 3.0, 1.0)
    assert len([e for e in recorder.events()
                if e["kind"] == "drift_page"]) == 1
    # Recovery: drift_ok event, state ok.
    aud.observe("kv_decode", 1.0, 1.0)
    assert not aud.paged()
    assert [e for e in recorder.events()
            if e["kind"] == "drift_ok"][-1]["series"] == "kv_decode"


def test_drift_auditor_unobservable_pairs():
    aud = DriftAuditor()
    assert aud.observe("s", 1.0, 0.0) is None     # no denominator yet
    assert aud.observe("s", -1.0, 1.0) is None    # nonsense modeled
    assert aud.ratios() == {} and aud.states() == {}


# -- the zero-overhead pin + audit on a live engine --------------------------


def test_steady_window_profiler_on_is_byte_identical(profiler):
    """THE overhead acceptance pin: 20 steady window steps with the
    plane ENABLED produce the exact same EngineStepCounters deltas as
    plane-off (the harvest rides first-seen shapes only — compile
    events, never the steady window) — and the audit over that run
    lands the kv_decode ratio INSIDE the one-sided band (modeled KV
    bytes are a component of XLA's totals, so honest means < band_hi)."""
    from dynamo_tpu.engine.sampling import SamplingParams

    def steady_run():
        core = _tiny_engine()
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):   # prefill + window warmup
            core.step()
        base = core.counters.snapshot()
        for _ in range(20):
            core.step()
        return core, core.counters.delta(base)

    profiler.enabled = False
    _, d_off = steady_run()
    profiler.enabled = True
    core_on, d_on = steady_run()
    assert d_on == d_off, (d_on, d_off)           # byte-identical
    assert d_on["window_dispatches"] == 20
    assert profiler.registry.size() > 0
    ratios = profiler.audit_engine(core_on)
    assert 0 < ratios["kv_decode"] <= profiler.auditor.band_hi
    assert all(st["state"] == "ok"
               for st in profiler.auditor.states().values())
    # audit_engine is scrape-time: it must not touch the engine counters.
    assert core_on.counters.delta(core_on.counters.snapshot()) \
        == {k: 0 for k in d_on}


def test_audit_engine_disabled_or_counterless_is_empty(profiler):
    profiler.enabled = False
    assert profiler.audit_engine(object()) == {}
    profiler.enabled = True
    assert profiler.audit_engine(object()) == {}


# -- surfaces ----------------------------------------------------------------


def test_metrics_lines_and_debug_payload(profiler):
    profiler.registry.record("window:True,1,16", flops=100.0,
                             bytes_accessed=1000.0)
    profiler.auditor.observe("kv_decode", 0.25, 1.0)
    lines = profiler.metrics_lines()
    text = "\n".join(lines)
    assert "dynamo_program_registry_size 1" in text
    assert ('dynamo_program_flops{program="window:True,1,16"} 100.0'
            in text)
    assert ('dynamo_program_bytes_accessed{program="window:True,1,16"} '
            '1000.0' in text)
    assert ('dynamo_modeled_vs_measured_ratio{series="kv_decode"} 0.25'
            in text)
    p = profiler.debug_payload()
    assert p["enabled"] is True and p["pid"] == os.getpid()
    assert p["registry_size"] == 1
    assert p["drift"]["kv_decode"]["state"] == "ok"
    assert p["captures"] == 0


def test_capture_disabled_refuses_and_enabled_lands_files(profiler,
                                                          tmp_path):
    profiler.enabled = False
    res = profiler.capture(50)
    assert res["ok"] is False and "disabled" in res["error"]
    profiler.enabled = True
    profiler.max_capture_ms = 60
    res = profiler.capture(5000)          # clamped to max_capture_ms
    assert res["ok"] is True, res
    assert res["ms"] == 60
    assert res["dir"].startswith(str(tmp_path))
    assert os.path.basename(res["dir"]) \
        == f"deviceprofile_test_{os.getpid()}"
    assert any(f.endswith(".trace.json.gz") for f in res["files"])
    meta = json.load(open(os.path.join(res["dir"], "capture_meta.json")))
    assert meta["service"] == "test" and meta["pid"] == os.getpid()
    assert meta["wall_end"] >= meta["wall_start"]
    assert profiler.captures == 1
    assert profiler.last_capture_dir == res["dir"]


def test_debug_deviceprofile_routes(profiler):
    """Both process surfaces serve the SAME payload shape (worker
    StatusServer + frontend HttpService); a bad/nonpositive ms is a
    400; ?ms= on a disabled plane is a 503 with the refusal."""
    import aiohttp

    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.service import ModelManager
    from dynamo_tpu.runtime.status import StatusServer

    profiler.registry.record("window:True,1,16", flops=1.0,
                             bytes_accessed=2.0)

    async def main():
        status = StatusServer()
        sport = await status.start()
        svc = HttpService(ModelManager())
        fport = await svc.start()
        try:
            async with aiohttp.ClientSession() as s:
                for port in (sport, fport):
                    async with s.get("http://127.0.0.1:%d"
                                     "/debug/deviceprofile" % port) as r:
                        assert r.status == 200
                        body = await r.json()
                    assert body["enabled"] is True
                    assert body["registry_size"] == 1
                    assert "window:True,1,16" in body["programs"]
                for bad in ("bogus", "0", "-5"):
                    async with s.get(
                            f"http://127.0.0.1:{sport}/debug/"
                            f"deviceprofile?ms={bad}") as r:
                        assert r.status == 400
                profiler.enabled = False
                async with s.get(f"http://127.0.0.1:{sport}"
                                 "/debug/deviceprofile?ms=50") as r:
                    assert r.status == 503
                    body = await r.json()
                    assert "disabled" in body["error"]
        finally:
            await svc.stop()
            await status.stop()

    asyncio.run(asyncio.wait_for(main(), 60))


# -- trace_merge --device ----------------------------------------------------


def _synth_capture(tmp_path, service="worker-backend", pid=1234,
                   wall_start=1000.0):
    """A minimal device-capture directory: sidecar + one gzipped Chrome
    trace with a lane-name metadata row, two X events, and one
    degenerate no-ph row (jax really emits those)."""
    cap = tmp_path / f"deviceprofile_{service}_{pid}"
    prof_dir = cap / "plugins" / "profile" / "2026_01_01_00_00_00"
    prof_dir.mkdir(parents=True)
    (cap / "capture_meta.json").write_text(json.dumps(
        {"service": service, "pid": pid, "ms": 50,
         "wall_start": wall_start, "wall_end": wall_start + 0.05}))
    doc = {"displayTimeUnit": "ns", "traceEvents": [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 10.0, "dur": 5.0,
         "name": "fusion.1"},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 20.0, "dur": 2.5,
         "name": "copy.2"},
        {},
    ]}
    with gzip.open(prof_dir / "host.trace.json.gz", "wt") as f:
        json.dump(doc, f)
    return str(cap)


def test_trace_merge_device_lanes_anchored_and_deduped(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_merge

    cap = _synth_capture(tmp_path, wall_start=1000.0)
    merged = trace_merge.merge_payloads([{
        "service": "worker-backend", "traces": [{
            "trace_id": "t1", "service": "worker-backend", "spans": [
                {"name": "engine.prefill", "trace_id": "t1",
                 "span_id": "s1", "parent_id": None,
                 "service": "worker-backend", "ts": 1000.0, "dur": 0.5,
                 "attrs": {}}]}]}])
    captures = trace_merge.load_device_capture(cap)
    assert len(captures) == 1
    assert captures[0]["service"] == "worker-backend"
    assert captures[0]["wall_start"] == 1000.0
    # Load the SAME capture twice: the dedup key must collapse it.
    added = trace_merge.merge_device_events(
        merged, captures + trace_merge.load_device_capture(cap))
    assert added == 2                       # X events only, once each
    dev = [e for e in merged["traceEvents"] if e.get("cat") == "device"]
    assert {e["name"] for e in dev} == {"fusion.1", "copy.2"}
    # Re-anchored onto the wall clock: wall_start µs + relative ts.
    fusion = next(e for e in dev if e["name"] == "fusion.1")
    assert fusion["ts"] == pytest.approx(1000.0 * 1e6 + 10.0)
    # The device lane is a fresh named track, distinct from host pids.
    lane_meta = [e for e in merged["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and "device/" in str((e.get("args") or {}).get("name"))]
    assert len(lane_meta) == 1
    assert lane_meta[0]["args"]["name"] \
        == "worker-backend device//device:TPU:0"
    assert all(e["pid"] == lane_meta[0]["pid"] for e in dev)
    host_pids = {e["pid"] for e in merged["traceEvents"]
                 if e.get("ph") == "X" and e.get("cat") != "device"}
    assert lane_meta[0]["pid"] not in host_pids


def test_load_device_capture_without_sidecar_uses_dirname(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_merge

    cap = _synth_capture(tmp_path, service="worker-prefill", pid=99)
    os.remove(os.path.join(cap, "capture_meta.json"))
    captures = trace_merge.load_device_capture(cap)
    assert captures[0]["service"] == "worker-prefill"
    assert captures[0]["wall_start"] is None
    # Un-anchored captures still merge (relative timestamps kept).
    merged = {"traceEvents": []}
    assert trace_merge.merge_device_events(merged, captures) == 2


def test_profile_trace_cli_exits_nonzero_without_trace_output(
        tmp_path, monkeypatch):
    """The retired-into-thin-CLI contract: a capture that lands no
    trace files must exit nonzero, not print an empty glob and read as
    success."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import profile_trace

    prof = device_profiler.get_profiler()
    monkeypatch.setattr(
        type(prof), "capture",
        lambda self, ms: {"ok": False, "error": "no plugin"})
    try:
        rc = profile_trace.main(
            ["--ms", "10", "--steps", "1", "--out-dir", str(tmp_path)])
    finally:
        prof.reset()
        prof.configure(enabled=False, service="dynamo")
        prof.dump_dir = None
    assert rc == 1


# -- live worker (slow) ------------------------------------------------------


@pytest.mark.slow
def test_deviceprofile_live_worker(tmp_path):
    """A REAL worker process serves the device-truth plane end to end:
    /metrics carries dynamo_program_registry_size, /debug/deviceprofile
    reports the plane enabled, a bad ms is a 400, and an on-demand
    ?ms=N capture lands real trace files under --flight-dump-dir in the
    deviceprofile_<service>_<pid> directory."""
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
             "--mocker", "--model-name", "dp-test", "--block-size", "8",
             "--flight-dump-dir", str(tmp_path)],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
        try:
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
                # The plane is on by default; the mocker compiles no
                # jitted programs, so the registry reports empty.
                assert "dynamo_program_registry_size 0" in metrics
                async with s.get(f"http://127.0.0.1:{sport}"
                                 "/debug/deviceprofile") as r:
                    assert r.status == 200
                    body = await r.json()
                assert body["enabled"] is True
                assert body["pid"] == proc.pid
                assert body["service"] == "worker-backend"
                async with s.get(f"http://127.0.0.1:{sport}"
                                 "/debug/deviceprofile?ms=nope") as r:
                    assert r.status == 400
                async with s.get(
                        f"http://127.0.0.1:{sport}"
                        "/debug/deviceprofile?ms=200",
                        timeout=aiohttp.ClientTimeout(total=60)) as r:
                    body = await r.json()
                    assert r.status == 200, body
                assert body["ok"] is True
                assert body["pid"] == proc.pid
                cap_dir = (tmp_path
                           / f"deviceprofile_worker-backend_{proc.pid}")
                assert str(cap_dir) == body["dir"]
                assert cap_dir.is_dir()
                assert (cap_dir / "capture_meta.json").exists()
                assert body["files"], body
        finally:
            proc.kill()
            proc.wait(timeout=20)
            log.close()
            await srv.stop()

    asyncio.run(asyncio.wait_for(main(), 150))
