"""`python -m dynamo_tpu.frontend` — OpenAI ingress + engine in one process.

Mirrors the reference frontend flags surface (`components/frontend/.../
main.py`: --http-port, --router-mode, ...) for the aggregated single-process
case; distributed modes (remote workers over the runtime's transports,
KV-aware routing across replicas) attach through the same ModelManager as
they land.

Engines:
  --mocker            mock engine (no device, KV-authentic; CI/demo)
  --model PRESET      real JAX engine on a model preset (random weights
                      unless --checkpoint)
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal

from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.llm.service import LocalEngineClient, ModelHandle, ModelManager
from dynamo_tpu.llm.tokenizer import ByteTokenizer, HFTokenizer

logger = logging.getLogger("dynamo_tpu.frontend")


def parse_args(argv=None):
    p = argparse.ArgumentParser("dynamo_tpu.frontend")
    p.add_argument("--http-host", default="127.0.0.1")
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--control-plane", default=None,
                   help="HOST:PORT of the control plane → distributed mode "
                        "(discover models from registered workers)")
    p.add_argument("--serve-control-plane", action="store_true",
                   help="also host the control-plane server in this process")
    p.add_argument("--control-plane-port", type=int, default=4222)
    p.add_argument("--control-plane-store", default=None,
                   help="with --serve-control-plane: persistence backend "
                        "('memory' or 'file:PATH' — unleased config "
                        "survives restarts; runtime/kv_store.py)")
    p.add_argument("--router-mode", default="round_robin",
                   choices=["round_robin", "random", "kv"])
    p.add_argument("--migration-limit", type=int, default=3)
    p.add_argument("--model-name", default="dynamo-tpu")
    p.add_argument("--out", default="auto",
                   help="backend (reference dynamo-run out= matrix, "
                        "`opt.rs:7-32`): auto|engine = in-process JAX "
                        "engine, echo streams the prompt back, mocker "
                        "simulates a vLLM-style engine, "
                        "dyn://ns/component/endpoint attaches a REMOTE "
                        "endpoint statically (no model discovery; needs "
                        "--control-plane)")
    p.add_argument("--mocker", action="store_true",
                   help="serve the mock engine (no accelerator)")
    p.add_argument("--model", default=None,
                   help="model preset name for the JAX engine "
                        "(e.g. llama-3-1b, tiny-test)")
    p.add_argument("--tokenizer", default=None,
                   help="path to a tokenizer.json (default: byte tokenizer)")
    p.add_argument("--num-blocks", type=int, default=512)
    p.add_argument("--block-size", type=int, default=64)
    p.add_argument("--kv-cache-block-size", type=int, default=None,
                   help="workers' KV block size for KV-aware routing in "
                        "dyn:// static mode (discovery mode reads it "
                        "from the model card; a mismatch silently zeroes "
                        "prefix-overlap scores)")
    p.add_argument("--max-tokens-default", type=int, default=512)
    p.add_argument("--speedup-ratio", type=float, default=10.0,
                   help="mocker simulated-time compression")
    p.add_argument("--input", default="http",
                   choices=["http", "text", "batch"],
                   help="ingress mode (reference dynamo-run in=http|text|"
                        "batch): http server, interactive REPL, or "
                        "offline JSONL batch")
    p.add_argument("--batch-file", default=None,
                   help="batch mode: JSONL input ({\"prompt\": ...})")
    p.add_argument("--batch-output", default=None,
                   help="batch mode: JSONL output (default: input + .out)")
    from dynamo_tpu.runtime.config import (
        apply_to_parser_defaults, load_layered_config)
    from dynamo_tpu.runtime.flight_recorder import add_flight_args
    from dynamo_tpu.runtime.ledger import add_ledger_args
    from dynamo_tpu.runtime.slo import add_slo_args
    from dynamo_tpu.runtime.tracing import add_trace_args

    add_trace_args(p)
    add_slo_args(p)
    add_flight_args(p)
    add_ledger_args(p)
    apply_to_parser_defaults(p, load_layered_config(
        {"http_host": "127.0.0.1", "http_port": 8080,
         "control_plane": None, "router_mode": "round_robin",
         "migration_limit": 3, "model_name": "dynamo-tpu",
         "num_blocks": 512, "block_size": 64},
        section="frontend"))
    args = p.parse_args(argv)
    # Validate --out here (choices= can't express the dyn:// prefix):
    # distributed mode never reaches build_model_handle, and a typo'd
    # backend selection must not be silently ignored.
    if args.out not in ("auto", "engine", "mocker", "echo") \
            and not args.out.startswith("dyn://"):
        p.error(f"--out {args.out!r}: expected auto|engine|mocker|echo|"
                "dyn://namespace/component/endpoint")
    return args


async def build_model_handle(args) -> tuple:
    """Returns (handle, shutdown coroutine).  Backend per the out=
    matrix (`--out`, reference dynamo-run `opt.rs:7-32`)."""
    out = args.out
    if args.mocker:
        out = "mocker"  # back-compat alias
    tokenizer = (HFTokenizer(args.tokenizer) if args.tokenizer
                 else ByteTokenizer())
    pre = OpenAIPreprocessor(tokenizer,
                             default_max_tokens=args.max_tokens_default)

    if out == "mocker":
        from dynamo_tpu.llm.mocker import MockEngine, MockEngineArgs

        engine = MockEngine(MockEngineArgs(
            block_size=args.block_size,
            speedup_ratio=args.speedup_ratio))
        await engine.start()
        handle = ModelHandle(name=args.model_name, tokenizer=tokenizer,
                             preprocessor=pre, client=engine)
        return handle, engine.stop

    if out == "echo":
        from dynamo_tpu.llm.echo import EchoEngine

        async def noop():
            return None

        handle = ModelHandle(name=args.model_name, tokenizer=tokenizer,
                             preprocessor=pre, client=EchoEngine())
        return handle, noop

    if out.startswith("dyn://"):
        # Static remote attachment (reference EngineConfig::StaticRemote,
        # dynamo-run out=dyn://): route to a known endpoint path without
        # model discovery — the card (and so tokenizer) stays local.
        if not args.control_plane:
            raise SystemExit("--out dyn://... needs --control-plane")
        parts = out[len("dyn://"):].split("/")
        if len(parts) != 3 or not all(parts):
            raise SystemExit(
                f"--out {out!r}: expected dyn://namespace/component/endpoint")
        from dynamo_tpu.runtime.control_plane_tcp import ControlPlaneClient
        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.runtime.pipeline import (
            KvRouterOp, MigrationOp, Pipeline, RemoteOp)

        host, _, port = args.control_plane.rpartition(":")
        cp = ControlPlaneClient(host or "127.0.0.1", int(port))
        await cp.start()
        runtime = DistributedRuntime(cp)
        endpoint = (runtime.namespace(parts[0]).component(parts[1])
                    .endpoint(parts[2]))
        client = await endpoint.client(args.router_mode
                                       if args.router_mode != "kv"
                                       else "round_robin")
        # Same operator graph as discovery mode — --router-mode kv gets
        # real KV-aware routing here too, not a silent downgrade.  The
        # block size must match the WORKERS' (discovery mode reads the
        # card; static mode can't, so it is a flag).
        if args.router_mode == "kv" and args.kv_cache_block_size is None:
            logger.warning(
                "dyn:// with --router-mode kv: assuming workers use "
                "--block-size %d; pass --kv-cache-block-size if not "
                "(a mismatch zeroes every prefix-overlap score)",
                args.block_size)
        router_op = (KvRouterOp(runtime,
                                block_size=(args.kv_cache_block_size
                                            or args.block_size))
                     if args.router_mode == "kv" else RemoteOp())
        pipeline = Pipeline([
            MigrationOp(limit=args.migration_limit), router_op,
        ])
        engine_client = await pipeline.attach(client)

        async def shutdown():
            await pipeline.stop()
            await client.stop()
            await runtime.shutdown()
            await cp.close()

        handle = ModelHandle(name=args.model_name, tokenizer=tokenizer,
                             preprocessor=pre, client=engine_client)
        return handle, shutdown

    if out not in ("auto", "engine"):
        raise SystemExit(f"unknown --out {out!r} (auto|engine|mocker|"
                         "echo|dyn://ns/component/endpoint)")

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore, InferenceEngine
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.models.loader import resolve_model
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache
    from dynamo_tpu.runtime.program_store import open_store

    cache_dir = enable_compile_cache()
    logger.info("compile cache: %s", cache_dir)
    cfg, params, tok_spec, template = resolve_model(
        args.model or "llama-3-1b")
    if args.tokenizer is None and tok_spec.get("kind") != "byte":
        # Real checkpoints carry their tokenizer + chat template; honor
        # them unless the operator overrode --tokenizer.
        card = ModelDeploymentCard(name=args.model_name,
                                   tokenizer_spec=tok_spec,
                                   chat_template=template)
        tokenizer = card.build_tokenizer()
        pre = OpenAIPreprocessor(tokenizer, chat_template=template,
                                 default_max_tokens=args.max_tokens_default)
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=args.num_blocks,
        program_store=open_store(cache_dir),
        scheduler=SchedulerConfig(block_size=args.block_size)),
        params=params)
    core.join_read_ahead()
    engine = InferenceEngine(core)
    await engine.start()
    # Single-process multimodal: image_url parts encode in-process (the
    # stub vision tower) — no encode worker needed for in= engine mode.
    from dynamo_tpu.llm.multimodal import MultimodalAttach, StubVisionEncoder

    handle = ModelHandle(name=args.model_name, tokenizer=tokenizer,
                         preprocessor=pre,
                         client=LocalEngineClient(engine),
                         max_context=cfg.max_context,
                         multimodal=MultimodalAttach(
                             local_encoder=StubVisionEncoder(
                                 cfg.hidden_size)))
    return handle, engine.stop


async def _wait_for_model(models: ModelManager, timeout: float = 30.0):
    import time as _time

    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        names = models.names()
        if names:
            return models.get(names[0])
        await asyncio.sleep(0.1)
    raise TimeoutError("no model became available")


async def run_text_repl(models: ModelManager) -> None:
    """Interactive chat REPL on stdin/stdout (reference `dynamo-run
    in=text`, `entrypoint/input/text.rs`).  One exchange per line; Ctrl-D
    or /quit exits; /clear resets the conversation."""
    from dynamo_tpu.llm.backend import StreamDetokenizer
    from dynamo_tpu.llm.protocols.openai import (
        ChatCompletionRequest, ChatMessage, request_id)

    handle = await _wait_for_model(models)
    print(f"chat with {handle.name!r} — /quit exits, /clear resets",
          flush=True)
    history = []
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, _read_prompt)
        if line is None or line.strip() == "/quit":
            return
        if line.strip() == "/clear":
            history = []
            print("(history cleared)", flush=True)
            continue
        if not line.strip():
            continue
        history.append(ChatMessage(role="user", content=line))
        body = ChatCompletionRequest(model=handle.name, messages=history)
        pre = handle.preprocessor.preprocess_chat(body, request_id("repl"))
        det = StreamDetokenizer(handle.tokenizer, pre.stop_sequences)
        parts = []
        async for delta in handle.client.generate(pre):
            if delta.token_ids:
                out = det.push_tokens(delta.token_ids)
                if out.text:
                    parts.append(out.text)
                    print(out.text, end="", flush=True)
                if out.finished:
                    break
            if delta.finished:
                break
        print(flush=True)
        history.append(ChatMessage(role="assistant",
                                   content="".join(parts)))


def _read_prompt():
    try:
        return input("> ")
    except EOFError:
        return None


async def _cancel_task(task) -> None:
    task.cancel()
    try:
        await task
    except asyncio.CancelledError:
        pass


async def run_batch(models: ModelManager, batch_file: str,
                    batch_output: str, concurrency: int = 32) -> dict:
    """Offline batch inference (reference `dynamo-run in=batch`,
    `entrypoint/input/batch.rs`): JSONL in ({"prompt", "max_tokens"?}),
    JSONL out (adds "completion", token counts), throughput summary."""
    import json
    import time as _time

    from dynamo_tpu.llm.backend import StreamDetokenizer
    from dynamo_tpu.llm.protocols.openai import CompletionRequest, request_id

    handle = await _wait_for_model(models)
    with open(batch_file) as f:
        jobs = [json.loads(line) for line in f if line.strip()]
    sem = asyncio.Semaphore(concurrency)
    results = [None] * len(jobs)
    t0 = _time.monotonic()

    async def one(i, job):
        async with sem:
            # One bad job (missing field, over-context prompt, worker
            # error) must not abort the other N-1: record the error in
            # its row and keep going — offline batches are restartable
            # only if the output file exists.
            try:
                body = CompletionRequest(
                    model=handle.name, prompt=job["prompt"],
                    max_tokens=job.get("max_tokens", 128),
                    temperature=job.get("temperature", 0.0))
                pre = handle.preprocessor.preprocess_completion(
                    body, request_id(f"batch{i}"))
                det = StreamDetokenizer(handle.tokenizer,
                                        pre.stop_sequences)
                parts = []
                async for delta in handle.client.generate(pre):
                    if delta.token_ids:
                        out = det.push_tokens(delta.token_ids)
                        if out.text:
                            parts.append(out.text)
                        if out.finished:
                            break
                    if delta.finished:
                        break
                results[i] = {**job, "completion": "".join(parts),
                              "prompt_tokens": len(pre.token_ids),
                              "completion_tokens": det.completion_tokens}
            except Exception as e:
                results[i] = {**job, "error": f"{type(e).__name__}: {e}",
                              "completion_tokens": 0}

    await asyncio.gather(*(one(i, j) for i, j in enumerate(jobs)))
    elapsed = _time.monotonic() - t0
    out_tokens = sum(r["completion_tokens"] for r in results)
    with open(batch_output, "w") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")
    summary = {"requests": len(jobs), "output_tokens": out_tokens,
               "elapsed_s": round(elapsed, 3),
               "tok_s": round(out_tokens / elapsed, 2) if elapsed else 0.0}
    print(json.dumps(summary), flush=True)
    return summary


async def run(args) -> None:
    from dynamo_tpu import native
    from dynamo_tpu.runtime import flight_recorder
    from dynamo_tpu.runtime.tracing import configure_from_args

    configure_from_args(args, service="frontend")
    # Flight recorder (ISSUE 14): the frontend's ring holds SLO state
    # transitions and slow-request markers; crash/SIGUSR2/atexit dumps
    # armed like any worker; /debug/flightrecorder serves it.
    flight_recorder.configure_from_args(
        args, service="frontend").install_crash_dump()
    # Request ledger (ISSUE 18): --request-ledger off disables every
    # stamp site process-wide.
    from dynamo_tpu.runtime import ledger as ledger_mod

    ledger_mod.configure_from_args(args)
    await native.warmup()  # build the C++ hasher off the event loop
    models = ModelManager()
    shutdowns = []
    # One registry for the whole frontend process: HTTP request series
    # AND router-side series (remote-prefix route counter) share one
    # /metrics exposition.
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    registry = MetricsRegistry()

    cp_server = None
    if args.serve_control_plane:
        from dynamo_tpu.runtime.control_plane import ControlPlaneState
        from dynamo_tpu.runtime.control_plane_tcp import ControlPlaneServer
        from dynamo_tpu.runtime.kv_store import make_backend

        cp_server = ControlPlaneServer(ControlPlaneState(
            backend=make_backend(args.control_plane_store)))
        port = await cp_server.start(port=args.control_plane_port)
        args.control_plane = args.control_plane or f"127.0.0.1:{port}"
        print(f"control plane on 127.0.0.1:{port}", flush=True)

    cp_client = None  # set in distributed mode: status-endpoint registration
    if args.out.startswith("dyn://") and not args.mocker:
        # Static remote attachment bypasses discovery entirely
        # (build_model_handle dials the endpoint itself; --mocker is a
        # back-compat alias that overrides --out, so it must not take
        # this branch under a 'static remote' banner).
        handle, shutdown = await build_model_handle(args)
        models.register(handle)
        shutdowns.append(shutdown)
        banner = f"static remote {args.out} as {handle.name!r}"
    elif args.control_plane:
        # Distributed mode: discover models from registered workers.
        from dynamo_tpu.llm.discovery import ModelWatcher
        from dynamo_tpu.runtime.control_plane_tcp import ControlPlaneClient
        from dynamo_tpu.runtime.distributed import DistributedRuntime

        host, _, port = args.control_plane.rpartition(":")
        cp = ControlPlaneClient(host, int(port))
        await cp.start()
        runtime = DistributedRuntime(cp)
        watcher = ModelWatcher(runtime, models, router_mode=args.router_mode,
                               migration_limit=args.migration_limit,
                               registry=registry)
        await watcher.start()
        shutdowns += [watcher.stop, runtime.shutdown, cp.close]
        cp_client = cp
        banner = f"discovering models via {args.control_plane}"
    else:
        handle, shutdown = await build_model_handle(args)
        models.register(handle)
        shutdowns.append(shutdown)
        banner = f"serving {handle.name!r}"

    svc = None
    # Signal handling covers every ingress mode: SIGTERM mid-batch or
    # mid-REPL must still run the shutdown path (engine drain, control
    # plane close) rather than die in the default handler.
    stop_ev = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop_ev.set)
    try:
        if args.input == "text":
            repl = asyncio.create_task(run_text_repl(models))
            stop_wait = asyncio.create_task(stop_ev.wait())
            await asyncio.wait({repl, stop_wait},
                               return_when=asyncio.FIRST_COMPLETED)
            repl.cancel()
            stop_wait.cancel()
        elif args.input == "batch":
            if not args.batch_file:
                raise SystemExit("--input batch requires --batch-file")
            batch = asyncio.create_task(run_batch(
                models, args.batch_file,
                args.batch_output or args.batch_file + ".out"))
            stop_wait = asyncio.create_task(stop_ev.wait())
            await asyncio.wait({batch, stop_wait},
                               return_when=asyncio.FIRST_COMPLETED)
            stop_wait.cancel()
            if batch.done():
                batch.result()  # surface batch errors
            else:
                batch.cancel()
        else:
            from dynamo_tpu.runtime.slo import monitor_from_args

            svc = HttpService(models, registry=registry)
            # Goodput attribution: the sink judges each request against
            # the same TTFT/TPOT thresholds the SLO objectives use, and
            # its dominant-phase window is the monitor's burn
            # attribution (PAGEs name the hop burning budget).
            svc.ledger_sink.slo_ttft = args.slo_ttft_p99
            svc.ledger_sink.slo_tpot = args.slo_tpot_p99
            # SLO burn-rate monitor over this frontend's request
            # histograms (--slo-* flags; /debug/slo + dynamo_slo_*
            # gauges on /metrics).
            slo_monitor = monitor_from_args(
                args, svc.request_metrics, registry=svc.registry,
                attribution_fn=svc.ledger_sink.dominant_phase)
            if slo_monitor is not None:
                svc.slo_monitor = slo_monitor
                slo_monitor.start(interval=args.slo_tick)
                shutdowns.append(slo_monitor.stop)
            port = await svc.start(args.http_host, args.http_port)
            if cp_client is not None:
                # Fleet discovery: the aggregator and `dynamo top` find
                # this frontend under status_endpoints/ like any worker.
                # Best-effort with retry — a control plane mid-restart
                # must not crash the frontend.
                from dynamo_tpu.runtime.status import (
                    register_status_endpoint_task)

                adv_host = args.http_host
                if adv_host in ("0.0.0.0", "::", ""):
                    # Wildcard binds are not scrapeable addresses; fall
                    # back to loopback (cross-host fleets should pass a
                    # routable --http-host, same rule as the worker's
                    # --rpc-host).
                    logger.warning(
                        "--http-host %s is a wildcard bind; advertising "
                        "127.0.0.1 under status_endpoints/ — pass a "
                        "routable --http-host for cross-host scraping",
                        adv_host)
                    adv_host = "127.0.0.1"
                reg_task = register_status_endpoint_task(
                    cp_client, "frontend", port, host=adv_host)
                shutdowns.append(lambda: _cancel_task(reg_task))
            print(f"dynamo_tpu frontend {banner} "
                  f"on http://{args.http_host}:{port}", flush=True)
            await stop_ev.wait()
    finally:
        if svc:
            await svc.stop()
        for fn in shutdowns:
            await fn()
        if cp_server:
            await cp_server.stop()


def main(argv=None) -> None:
    logging.basicConfig(level=logging.INFO)
    asyncio.run(run(parse_args(argv)))


if __name__ == "__main__":
    main()
