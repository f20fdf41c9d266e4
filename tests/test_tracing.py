"""Distributed request tracing (runtime/tracing.py): context propagation
across an RPC hop, sampling, ring-buffer bounds, slow-request force
sampling, Chrome trace-event export, and the frontend+worker e2e merged
trace the ISSUE acceptance names.
"""

import asyncio
import json
import math

import pytest

from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.tracing import TraceContext, Tracer, chrome_trace


def _run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    tr = tracing.get_tracer()
    tr.enabled = False
    tr.sampling = 1.0
    tr.slow_ms = None
    tr.slow_log_path = None
    tr.reset()
    yield
    tr.enabled = False
    tr.sampling = 1.0
    tr.slow_ms = None
    tr.slow_log_path = None
    tr.reset()


# ---------------------------------------------------------------------------
# TraceContext wire format


def test_context_wire_roundtrip():
    ctx = TraceContext("tid1", "sid1", sampled=True)
    child = ctx.child()
    assert child.trace_id == "tid1"
    assert child.parent_id == "sid1"
    assert child.span_id != "sid1"
    back = TraceContext.from_wire(ctx.to_wire())
    assert back.trace_id == "tid1" and back.span_id == "sid1"
    assert back.sampled is True


def test_context_from_wire_malformed():
    for bad in (None, 42, "x", {}, {"trace_id": "t"}, {"span_id": "s"},
                {"trace_id": "", "span_id": "s"}):
        assert TraceContext.from_wire(bad) is None


# ---------------------------------------------------------------------------
# Tracer core: parenting, sampling, bounds, slow force-sampling


def test_span_parenting_and_finalize():
    tr = Tracer("svc", enabled=True)
    root = tr.start_span("root", trace_id="rid")
    child = tr.start_span("child", root)
    grand = tr.start_span("grand", child)
    grand.end()
    child.end()
    assert tr.completed() == []          # root still open: not finalized
    root.end()
    traces = tr.completed()
    assert len(traces) == 1
    spans = {s["name"]: s for s in traces[0]["spans"]}
    assert spans["child"]["parent_id"] == spans["root"]["span_id"]
    assert spans["grand"]["parent_id"] == spans["child"]["span_id"]
    assert all(s["trace_id"] == "rid" for s in traces[0]["spans"])


def test_disabled_tracer_is_noop():
    tr = Tracer("svc", enabled=False)
    span = tr.start_span("root")
    assert span is tracing.NULL_SPAN
    span.set_attr(x=1)
    span.end()
    assert tr.completed() == []
    assert tr.spans_recorded == 0


def test_sampling_honors_rate():
    tr = Tracer("svc", enabled=True, sampling=0.3, ring_size=4096)
    n = 600
    for _ in range(n):
        tr.start_span("root").end()
    kept = len(tr.completed())
    # Deterministic per-trace-id hash sampling over uuid ids: binomial
    # around 0.3 (sd ~11 at n=600); ±0.1 absolute is > 5 sd.
    assert 0.2 * n < kept < 0.4 * n, kept
    assert tr.traces_dropped_unsampled == n - kept


def test_sampling_is_deterministic_per_trace_id():
    tr = Tracer("svc", enabled=True, sampling=0.5)
    decisions = {tid: tr.start_span("r", trace_id=tid).ctx.sampled
                 for tid in ("a1", "b2", "c3", "d4")}
    for tid, want in decisions.items():
        again = tr.start_span("r", trace_id=tid)
        assert again.ctx.sampled is want


def test_ring_buffer_bounds_memory():
    tr = Tracer("svc", enabled=True, ring_size=8)
    for i in range(50):
        tr.start_span("root", trace_id=f"t{i}").end()
    traces = tr.completed()
    assert len(traces) == 8
    # Newest first, oldest evicted.
    assert traces[0]["trace_id"] == "t49"
    assert not tr._pending


def test_per_trace_span_cap():
    tr = Tracer("svc", enabled=True, max_spans_per_trace=16)
    root = tr.start_span("root", trace_id="big")
    for i in range(100):
        tr.start_span(f"s{i}", root).end()
    root.end()
    (trace,) = tr.completed()
    assert len(trace["spans"]) == 16
    assert trace["spans_dropped"] == 85  # 100 subs + root − 16 kept


def test_slow_request_force_sampling_fires(tmp_path):
    log = tmp_path / "slow.jsonl"
    tr = Tracer("svc", enabled=True, sampling=0.0, slow_ms=5.0,
                slow_log_path=str(log))
    # Fast + unsampled: dropped entirely.
    tr.start_span("root", trace_id="fast").end()
    assert tr.completed() == []
    # Slow + unsampled: force-kept and logged as structured JSONL.
    span = tr.start_span("root", trace_id="slow-one",
                         attrs={"rid": "slow-one", "model": "m"})
    import time

    time.sleep(0.02)
    span.end()
    (trace,) = tr.completed()
    assert trace["trace_id"] == "slow-one"
    assert trace["forced_slow_sample"] is True
    assert tr.traces_forced_slow == 1
    lines = [json.loads(line) for line in log.read_text().splitlines()]
    assert len(lines) == 1
    assert lines[0]["event"] == "slow_request"
    assert lines[0]["trace_id"] == "slow-one"
    assert lines[0]["duration_ms"] > 5.0
    assert lines[0]["attrs"]["model"] == "m"


def test_record_span_binding():
    """The engine-thread path: bind rid → ctx, record measured spans."""
    import time

    tr = Tracer("svc", enabled=True)
    root = tr.start_span("root", trace_id="rid")
    tr.bind("req-1", root.ctx)
    t0 = time.monotonic() - 0.25
    tr.record_span("engine.ttft", tr.ctx_for("req-1"), t0,
                   attrs={"request_id": "req-1"})
    tr.unbind("req-1")
    assert tr.ctx_for("req-1") is None
    root.end()
    (trace,) = tr.completed()
    spans = {s["name"]: s for s in trace["spans"]}
    assert spans["engine.ttft"]["parent_id"] == spans["root"]["span_id"]
    assert 0.2 < spans["engine.ttft"]["dur"] < 2.0


# ---------------------------------------------------------------------------
# Chrome trace-event export


def test_chrome_trace_export_is_valid():
    tr = Tracer("frontend", enabled=True)
    root = tr.start_span("http.chat", trace_id="rid")
    tr.start_span("router.select", root).end()
    root.end()
    out = chrome_trace(tr.completed())
    text = json.dumps(out)              # serializable
    parsed = json.loads(text)
    events = parsed["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert len(xs) == 2 and len(ms) == 1
    for e in xs:
        assert isinstance(e["ts"], (int, float)) and e["ts"] > 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["trace_id"] == "rid"
    assert ms[0]["name"] == "process_name"
    assert ms[0]["args"]["name"] == "frontend"


def test_chrome_trace_dedupes_spans_across_payloads():
    tr = Tracer("svc", enabled=True)
    tr.start_span("root", trace_id="rid").end()
    traces = tr.completed()
    out = chrome_trace(traces + traces)   # same payload twice
    assert sum(1 for e in out["traceEvents"] if e["ph"] == "X") == 1


def test_trace_merge_payloads():
    from tools.trace_merge import merge_payloads

    f = Tracer("frontend", enabled=True)
    w = Tracer("worker", enabled=True)
    root = f.start_span("http.chat", trace_id="rid")
    client = f.start_span("rpc.client:generate", root)
    # Worker side parents off the wire context.
    ctx = TraceContext.from_wire(client.ctx.to_wire())
    server = w.start_span("rpc.server:generate", ctx)
    server.end()
    client.end()
    root.end()
    merged = merge_payloads([
        {"service": "frontend", "traces": f.completed()},
        {"service": "worker", "traces": w.completed()},
    ])
    xs = {e["name"]: e for e in merged["traceEvents"] if e["ph"] == "X"}
    assert set(xs) == {"http.chat", "rpc.client:generate",
                       "rpc.server:generate"}
    # One trace, two processes, parent chain intact across the hop.
    assert xs["rpc.server:generate"]["args"]["parent_id"] == \
        xs["rpc.client:generate"]["args"]["span_id"]
    assert xs["rpc.server:generate"]["pid"] != xs["http.chat"]["pid"]
    assert len({e["args"]["trace_id"] for e in
                merged["traceEvents"] if e["ph"] == "X"}) == 1


# ---------------------------------------------------------------------------
# RPC hop propagation (real RpcServer/RpcClient)


def test_rpc_hop_client_span_parents_server_span():
    from dynamo_tpu.runtime.rpc import RpcClient, RpcServer

    tr = tracing.get_tracer()
    tr.configure(enabled=True, sampling=1.0)

    async def main():
        server = RpcServer()

        async def handler(payload):
            # Worker-side sub-span under the server span (the engine
            # analog); the current span must be the rpc.server span.
            span = tracing.current_span()
            assert span is not None and span.name == "rpc.server:gen"
            with tr.start_span("work"):
                yield {"ok": 1}

        server.register("gen", handler)
        addr = await server.start()
        client = RpcClient(addr)
        root = tr.start_span("root", trace_id="rid-hop")
        tok = tracing.use_span(root)
        try:
            deltas = [d async for d in client.call("gen", {})]
        finally:
            tracing.restore(tok)
        assert deltas == [{"ok": 1}]
        # Server-side span end races the client's stream end; wait for
        # the server task to settle before closing the trace.
        for _ in range(100):
            if not server.active_streams:
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.02)
        root.end()
        await client.close()
        await server.stop()
        for _ in range(100):
            if tr.completed():
                break
            await asyncio.sleep(0.01)
        return tr.completed()

    traces = _run(main())
    assert len(traces) == 1
    spans = {s["name"]: s for s in traces[0]["spans"]}
    assert set(spans) == {"root", "rpc.client:gen", "rpc.server:gen",
                          "work"}
    assert all(s["trace_id"] == "rid-hop" for s in spans.values())
    assert spans["rpc.client:gen"]["parent_id"] == spans["root"]["span_id"]
    assert spans["rpc.server:gen"]["parent_id"] == \
        spans["rpc.client:gen"]["span_id"]
    assert spans["work"]["parent_id"] == spans["rpc.server:gen"]["span_id"]


def test_rpc_without_trace_field_still_works():
    from dynamo_tpu.runtime.rpc import RpcClient, RpcServer

    async def main():
        server = RpcServer()

        async def handler(payload):
            yield {"v": payload["x"] + 1}

        server.register("inc", handler)
        addr = await server.start()
        client = RpcClient(addr)
        out = [d async for d in client.call("inc", {"x": 1})]
        await client.close()
        await server.stop()
        return out

    assert _run(main()) == [{"v": 2}]


# ---------------------------------------------------------------------------
# Histogram edge behavior (satellite)


def test_histogram_nan_safe_edges():
    from dynamo_tpu.runtime.metrics import LATENCY_BUCKETS, Histogram

    h = Histogram("x", "")
    assert math.isnan(h.quantile(0.5))
    assert math.isnan(h.mean())
    assert math.isnan(h.quantile(0.0, labels={"model": "nope"}))
    h.observe(0.003)
    # Single observation answers every quantile with its own bucket.
    assert h.quantile(0.0) == h.quantile(0.5) == h.quantile(1.0) == 0.005
    assert h.quantile(-3.0) == h.quantile(7.5) == 0.005  # clamped
    h.observe(1e9)  # beyond the last bucket
    assert h.quantile(1.0) == float("inf")
    # Sub-ms resolution exists and the top covers a minute.
    assert LATENCY_BUCKETS[0] <= 0.0001 and LATENCY_BUCKETS[-1] >= 60.0


# ---------------------------------------------------------------------------
# End-to-end: frontend + worker over RPC → merged Perfetto trace


def test_e2e_frontend_worker_merged_trace():
    """The ISSUE acceptance scenario: a streamed chat request through
    HttpService → KV router → RPC → worker engine produces ONE trace with
    parented spans for routing, queue wait, prefill, and ≥3 decode token
    intervals; /metrics reports nonzero dynamo_request_ttft_seconds; the
    merged Chrome JSON from frontend + worker /debug/traces buffers loads
    as one timeline."""
    import aiohttp

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore, \
        InferenceEngine
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.llm.discovery import (
        ModelWatcher, engine_wire_handler, register_llm)
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.service import LocalEngineClient, ModelManager
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime.control_plane_tcp import (
        ControlPlaneClient, ControlPlaneServer)
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.status import StatusServer
    from tools.trace_merge import merge_payloads

    tr = tracing.get_tracer()
    tr.configure(enabled=True, sampling=1.0)

    async def main():
        cp_server = ControlPlaneServer()
        cp_port = await cp_server.start()

        # -- worker side (engine behind an RPC endpoint) ------------------
        wcp = ControlPlaneClient("127.0.0.1", cp_port)
        await wcp.start()
        wruntime = DistributedRuntime(wcp)
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=128,
            decode_window=1,   # one delta per token → real TPOT intervals
            scheduler=SchedulerConfig(
                max_seqs=4, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=128, decode_buckets=(1, 2, 4),
                prefill_buckets=(16, 128))))
        engine = InferenceEngine(core)
        await engine.start()
        endpoint = (wruntime.namespace("dynamo").component("backend")
                    .endpoint("generate"))
        instance = await endpoint.serve(
            engine_wire_handler(LocalEngineClient(engine)))
        await register_llm(endpoint, instance, ModelDeploymentCard(
            name="traced-model", kv_block_size=8))
        worker_status = StatusServer()
        worker_port = await worker_status.start()

        # -- frontend side (discovery + KV routing + HTTP) ----------------
        fcp = ControlPlaneClient("127.0.0.1", cp_port)
        await fcp.start()
        fruntime = DistributedRuntime(fcp)
        models = ModelManager()
        watcher = ModelWatcher(fruntime, models, router_mode="kv")
        await watcher.start()
        await watcher.wait_for_model("traced-model")
        svc = HttpService(models)
        http_port = await svc.start()

        try:
            async with aiohttp.ClientSession() as session:
                async with session.post(
                        f"http://127.0.0.1:{http_port}/v1/chat/completions",
                        json={"model": "traced-model",
                              "messages": [{"role": "user",
                                            "content": "hello trace"}],
                              "max_tokens": 10, "stream": True}) as resp:
                    assert resp.status == 200
                    body = await resp.text()
                    assert "data: [DONE]" in body

                async with session.get(
                        f"http://127.0.0.1:{http_port}/metrics") as resp:
                    metrics_text = await resp.text()

                # Both processes' trace buffers (shared tracer here; the
                # merge dedupes by span id exactly as it must for
                # co-located processes).
                async with session.get(
                        f"http://127.0.0.1:{http_port}/debug/traces?n=8"
                        ) as resp:
                    frontend_payload = await resp.json()
                async with session.get(
                        f"http://127.0.0.1:{worker_port}/debug/traces?n=8"
                        ) as resp:
                    worker_payload = await resp.json()
        finally:
            await svc.stop()
            await worker_status.stop()
            await watcher.stop()
            await endpoint.leave()
            await engine.stop()
            await fruntime.shutdown()
            await fcp.close()
            await wruntime.shutdown()
            await wcp.close()
            await cp_server.stop()
        return metrics_text, frontend_payload, worker_payload

    metrics_text, frontend_payload, worker_payload = _run(main(), 300)

    # Lifecycle histograms on /metrics: nonzero TTFT counts.
    assert "dynamo_request_ttft_seconds" in metrics_text
    count_lines = [ln for ln in metrics_text.splitlines()
                   if ln.startswith("dynamo_request_ttft_seconds_count")]
    assert count_lines and float(count_lines[0].rsplit(" ", 1)[1]) >= 1
    assert "dynamo_request_tpot_seconds" in metrics_text
    # HTTP entry -> stream start is the ledger's `receive` phase (the
    # histogram that doubled it under another name is gone).
    assert "dynamo_request_queue_wait_seconds" not in metrics_text
    receive = [ln for ln in metrics_text.splitlines() if ln.startswith(
        'dynamo_request_phase_seconds_count{phase="receive"}')]
    assert receive and float(receive[0].rsplit(" ", 1)[1]) >= 1

    # One merged trace with every hop.
    assert frontend_payload["traces"], frontend_payload
    merged = merge_payloads([frontend_payload, worker_payload])
    xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    by_name: dict = {}
    for e in xs:
        by_name.setdefault(e["name"], []).append(e)
    # RPC spans carry the full endpoint path (dynamo/backend/generate).
    rpc_client = "rpc.client:dynamo/backend/generate"
    rpc_server = "rpc.server:dynamo/backend/generate"
    for needed in ("http.chat", "router.select", rpc_client, rpc_server,
                   "frontend.queue_wait", "engine.queue_wait",
                   "engine.prefill", "engine.ttft", "frontend.ttft",
                   "decode.tpot"):
        assert needed in by_name, (needed, sorted(by_name))
    assert len(by_name["decode.tpot"]) >= 3

    # Parent chain: everything rolls up to the single request trace.
    trace_ids = {e["args"]["trace_id"] for e in xs}
    assert len(trace_ids) == 1
    spans = {e["args"]["span_id"]: e for e in xs}
    root = by_name["http.chat"][0]
    assert root["args"]["parent_id"] is None
    assert by_name["router.select"][0]["args"]["parent_id"] == \
        root["args"]["span_id"]
    assert by_name[rpc_server][0]["args"]["parent_id"] == \
        by_name[rpc_client][0]["args"]["span_id"]
    assert by_name["engine.prefill"][0]["args"]["parent_id"] == \
        by_name[rpc_server][0]["args"]["span_id"]
    for e in xs:   # every non-root parent resolves within the trace
        pid = e["args"]["parent_id"]
        assert pid is None or pid in spans
    # And the whole thing is valid, loadable JSON.
    json.loads(json.dumps(merged))
