"""`python -m dynamo_tpu.worker` — a backend worker process.

Reference analog: `dynamo.vllm`/`dynamo.mocker` mains — connect to the
control plane, serve the engine endpoint, `register_llm`, publish KV
events + load metrics, drain gracefully on SIGTERM (SURVEY.md §3.2).

    python -m dynamo_tpu.worker --control-plane HOST:PORT --mocker
    python -m dynamo_tpu.worker --control-plane HOST:PORT --model tiny-test
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal

from dynamo_tpu.llm.discovery import engine_wire_handler, register_llm
from dynamo_tpu.llm.kv_router.protocols import RouterEvent
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.runtime.contracts import never_engine_thread
from dynamo_tpu.runtime.control_plane_tcp import ControlPlaneClient
from dynamo_tpu.runtime.distributed import DistributedRuntime

logger = logging.getLogger("dynamo_tpu.worker")

KV_EVENTS_SUBJECT = "kv_events"        # reference kv_router.rs:56
METRICS_SUBJECT = "load_metrics"       # reference stats endpoint name


def parse_args(argv=None):
    from dynamo_tpu.runtime.config import (
        apply_to_parser_defaults, load_layered_config)

    p = argparse.ArgumentParser(
        "dynamo_tpu.worker",
        description="Layered config: defaults < dynamo.toml [worker] "
                    "section < DYN_* env < these flags "
                    "(runtime/config.py).")
    p.add_argument("--control-plane", default=None,
                   help="control plane HOST:PORT")
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--model-name", default="dynamo-tpu")
    p.add_argument("--role", choices=("both", "prefill", "decode", "encode"),
                   default="both",
                   help="disaggregated P/D role: 'prefill' serves the "
                        "prefill queue only (no model registration); "
                        "'decode' registers the model and sends long "
                        "prompts to the prefill queue; 'both' = aggregated; "
                        "'encode' serves the multimodal vision tower "
                        "(encoder/encode endpoint, reference "
                        "multimodal_v1 encode_worker)")
    p.add_argument("--max-local-prefill", type=int, default=None,
                   help="decode role: write the disagg threshold (tokens) "
                        "to the control plane at startup; prompts longer "
                        "than this prefill remotely.  The key is watched, "
                        "so operators can retune it live.")
    p.add_argument("--no-eager-kv", action="store_true",
                   help="decode role: disable eager KV-block streaming "
                        "(pull the whole sealed prefix only after the "
                        "prefill-done announcement, the pre-streaming "
                        "serial protocol)")
    p.add_argument("--no-prefix-share", action="store_true",
                   help="disable fleet-wide prefix reuse: ignore the "
                        "router's remote-prefix hints instead of pulling "
                        "a peer's sealed prefix blocks before prefill "
                        "(this worker still serves kv_blocks as a donor)")
    p.add_argument("--mocker", action="store_true")
    p.add_argument("--model", default=None,
                   help="model preset name (random weights) or HF-layout "
                        "checkpoint directory (real weights + tokenizer)")
    p.add_argument("--num-blocks", type=int, default=512)
    p.add_argument("--block-size", type=int, default=64)
    p.add_argument("--max-context", type=int, default=8192,
                   help="longest context (prompt + generated tokens) a "
                        "sequence may reach; sets the block tables' width "
                        "(ceil(N / block size) pages).  A request that "
                        "could outgrow it is refused at admission")
    p.add_argument("--max-prefill-chunk", type=int, default=512,
                   help="chunked-prefill step ceiling (tokens).  Prefill "
                        "workers seal + announce blocks per chunk, so "
                        "smaller chunks mean finer-grained eager KV "
                        "streaming at the cost of more prefill steps")
    # Declarative slice spec (ISSUE 16, fleet/topology.py): ONE string
    # naming the worker's mesh, KV mode, role and plane features —
    # expanded over the loose flags below after parsing, published in
    # the instance record, and consumed by make_sharded_step via the
    # same EngineConfig path.  The loose flags keep working; --slice is
    # the form the planner's role_worker_args and deploy tooling emit.
    p.add_argument("--slice", default=None, metavar="SPEC",
                   help="declarative slice spec, e.g. "
                        "'sp2xtp2,int8,packed,role=prefill' or "
                        "'tp2,int8,role=decode' — mesh descriptor + kv "
                        "mode + role + features (packed/spec/windowN/"
                        "dp_attention); overrides the corresponding "
                        "--tp/--sp/--pp/--kv-quant/--role flags")
    # Parallelism as a serving capability (reference: one-flag TP,
    # `components/backends/sglang/launch/disagg.sh:25`): degrees multiply
    # to the device count; the worker builds the mesh and the engine
    # shards params/cache/step over it.
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree (heads/features over ICI)")
    p.add_argument("--dp", type=int, default=1,
                   help="engine-internal data-parallel degree (batch axis)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (MoE models)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel degree: whole-prompt prefills "
                        "past the engine's threshold run ring attention "
                        "over the ICI ring (the long-context prefill "
                        "path); decode stays on the tp/dp plane")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel degree (GPipe stage-rotated "
                        "step).  Decode rides the fused stage programs "
                        "(all-in-one greedy step + schedule-looping "
                        "windows) and --kv-quant composes via stacked "
                        "scale buffers; the remaining impossible combos "
                        "(spec decode, multimodal embeds, "
                        "/v1/embeddings) reject with the capability "
                        "table's pointed errors")
    p.add_argument("--pp-microbatches", type=int, default=2,
                   help="GPipe microbatch count for the pp stage "
                        "schedule (batch rows pad to a multiple of it)")
    p.add_argument("--dp-attention", action="store_true",
                   help="batch-sharded attention with slot-sharded KV "
                        "(tp beyond the kv-head count; reference sglang "
                        "--enable-dp-attention)")
    # Multi-host: one EngineCore spanning N processes (SPMD lockstep,
    # parallel/multihost.py; reference srun_disaggregated.sh / LWS
    # multinode).  All ranks take IDENTICAL flags; rank 0 serves, ranks
    # 1..N-1 follow.  --coordinator/--num-processes/--process-id are
    # consumed by worker/__main__.py BEFORE jax init.
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator HOST:PORT "
                        "(multihost; all ranks pass the same value)")
    p.add_argument("--num-processes", type=int, default=1)
    p.add_argument("--process-id", type=int, default=0)
    p.add_argument("--lockstep", default=None,
                   help="leader's lockstep channel HOST:PORT (followers "
                        "connect; the leader binds the PORT part)")
    p.add_argument("--multihost-cpu-devices", type=int, default=0,
                   help="CPU test rig: force N virtual CPU devices + "
                        "gloo collectives in this process")
    p.add_argument("--decode-window", type=int, default=8,
                   help="fused decode window length (1 disables)")
    p.add_argument("--kv-quant", choices=("none", "int8"), default="none",
                   help="KV-cache storage mode: 'int8' stores pages as "
                        "int8 with per-token-per-head f32 scales and "
                        "dequantizes inside the decode kernel — ~0.53x "
                        "the HBM bytes per context token at serving "
                        "geometry.  Composes with every mesh (tp/dp/"
                        "dp-attention/sp/pp/multihost — ISSUE 12); "
                        "prefill and decode workers of one disagg pair "
                        "must match (mismatched peers refuse block "
                        "transfer loudly)")
    p.add_argument("--moe-mode",
                   choices=("auto", "dense", "grouped", "dispatch"),
                   default="auto",
                   help="MoE compute mode (dense models ignore it): "
                        "'auto' picks the grouped Pallas kernel on "
                        "meshless TPU engines and ep all-to-all dispatch "
                        "on ep>1 meshes; explicit rungs pin one — "
                        "'grouped' is meshless-only, 'dispatch' needs an "
                        "ep mesh (tp>1 composes: expert MLPs tp-shard "
                        "inside the dispatch body)")
    p.add_argument("--moe-capacity", type=int, default=None, metavar="C",
                   help="bounded per-expert dispatch capacity (tokens "
                        "per expert per source shard).  Default None = "
                        "EXACT routing, nothing dropped.  A bound "
                        "shrinks the all-to-all buffers; overflow "
                        "assignments are DROPPED and counted in "
                        "dynamo_moe_dropped_tokens_total, never silent")
    p.add_argument("--spec-decode", type=int, default=0, metavar="K",
                   help="self-speculative decoding: draft K tokens per "
                        "decode step (prompt-lookup n-gram drafter) and "
                        "verify them in one batched forward.  Greedy "
                        "output is byte-identical to K=0; stochastic "
                        "requests keep their exact sampling distribution "
                        "(rejection-sampling fallback).  0 disables")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="n-gram length for the prompt-lookup drafter")
    p.add_argument("--packed-prefill", choices=("auto", "on", "off"),
                   default="auto",
                   help="packed ragged prefill plane: chunks pack into "
                        "one flat token axis with per-segment block "
                        "tables and attention streams pages from the "
                        "pool via the Pallas flash-prefill kernel.  "
                        "'auto' = on for TPU meshless engines (MoE "
                        "included) whose geometry passes the Mosaic "
                        "eligibility rule; 'on' forces it (interpret "
                        "mode off-TPU); 'off' keeps the padded gather "
                        "plane")
    p.add_argument("--prewarm-prefill", action="store_true",
                   help="compile the packed prefill shape set at "
                        "startup (through the persistent XLA compile "
                        "cache) so the first request's TTFT doesn't pay "
                        "the cold-prefill compile cliff; no-op when the "
                        "packed plane is off")
    p.add_argument("--speedup-ratio", type=float, default=10.0)
    p.add_argument("--metrics-interval", type=float, default=1.0)
    p.add_argument("--health-port", type=int, default=0,
                   help="per-worker status server port (0 = ephemeral; "
                        "-1 disables; reference system_status_server.rs)")
    p.add_argument("--hbm-poll-interval", type=float, default=10.0,
                   help="seconds between HBM occupancy polls "
                        "(jax device memory_stats; engine workers "
                        "only, and absent on backends that report "
                        "none).  0 disables the poller.")
    p.add_argument("--rpc-host", default="127.0.0.1",
                   help="bind + ADVERTISED host for this worker's RPC "
                        "server; cross-host deployments must set a "
                        "routable address (K8s manifests inject the pod "
                        "IP) — the 127.0.0.1 default only works "
                        "single-host")
    p.add_argument("--drain", choices=("on", "off"), default="on",
                   help="SIGTERM drain with live KV migration (ISSUE "
                        "15): leave routing instantly, hand each "
                        "in-flight stream to a peer WITH its sealed KV "
                        "(migrate delta + kv_blocks pull), linger for "
                        "the peers' pulls, then exit.  'off' restores "
                        "the wait-out-every-stream SIGTERM.  The "
                        "control-plane key drain/<pid> (or "
                        "drain/instance/<id>) triggers the same drain "
                        "without a signal")
    p.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="bound on each drain phase (stream handoff; "
                        "peer KV pulls): past it the worker exits "
                        "anyway — peers fall back to re-prefill, "
                        "requests still survive")
    p.add_argument("--drain-linger-s", type=float, default=1.0,
                   help="grace after the last stream handoff for peers "
                        "to OPEN their KV pulls before the worker "
                        "starts watching for zero active streams")
    from dynamo_tpu.runtime.device_profiler import add_device_profiler_args
    from dynamo_tpu.runtime.flight_recorder import add_flight_args
    from dynamo_tpu.runtime.ledger import add_ledger_args
    from dynamo_tpu.runtime.slo import add_slo_args
    from dynamo_tpu.runtime.tracing import add_trace_args

    add_trace_args(p)
    add_slo_args(p)
    add_flight_args(p)
    add_ledger_args(p)
    add_device_profiler_args(p)
    apply_to_parser_defaults(p, load_layered_config(
        {"control_plane": None, "namespace": "dynamo",
         "component": "backend", "endpoint": "generate",
         "model_name": "dynamo-tpu", "num_blocks": 512, "block_size": 64,
         "metrics_interval": 1.0},
        section="worker"))
    args = p.parse_args(argv)
    if args.slice:
        try:
            _apply_slice_spec(args)
        except ValueError as e:
            p.error(str(e))
    if not args.control_plane and args.process_id == 0:
        p.error("--control-plane is required (flag, DYN_CONTROL_PLANE, "
                "or dynamo.toml)")
    return args


def _apply_slice_spec(args) -> None:
    """Expand `--slice` over the loose mesh/plane flags — the ONE
    declarative source the engine config, the published instance record
    and the planner's per-role spawn all agree on."""
    from dynamo_tpu.fleet.topology import parse_slice

    spec = parse_slice(args.slice)
    args.dp, args.pp, args.sp, args.ep, args.tp = spec.mesh
    args.role = spec.role
    args.kv_quant = spec.kv_quant if spec.kv_quant != "none" else "none"
    feats = set(spec.features)
    if "packed_prefill" in feats:
        args.packed_prefill = "on"
    if "dp_attention" in feats:
        args.dp_attention = True
    if "spec" in feats and getattr(args, "spec_decode", 0) <= 0:
        args.spec_decode = 3
    for f in feats:
        if f.startswith("window"):
            args.decode_window = int(f[len("window"):])


def derive_slice_spec(args, fabric: str = ""):
    """The SliceSpec this worker PUBLISHES (instance record metadata +
    status registration): mesh degrees, role, kv mode and plane features
    from the resolved flags, per-chip HBM, and the device-fabric id the
    transfer plane answers on.

    HBM is asked of the device only by a worker that hosts an engine.  A
    `--mocker` worker publishes 0 without touching JAX: initialising a
    backend there would take the chip from the engine worker beside it.
    A backend without memory stats (CPU) reports 0 as well."""
    from dynamo_tpu.fleet.topology import SliceSpec

    feats = []
    if getattr(args, "packed_prefill", "auto") == "on":
        feats.append("packed_prefill")
    if getattr(args, "dp_attention", False):
        feats.append("dp_attention")
    if getattr(args, "spec_decode", 0) > 0:
        feats.append("spec")
    if getattr(args, "decode_window", 1) > 1:
        feats.append(f"window{args.decode_window}")
    hbm = 0
    if not args.mocker:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        hbm = int(stats.get("bytes_limit", 0))
    return SliceSpec(
        mesh=(args.dp, getattr(args, "pp", 1), getattr(args, "sp", 1),
              args.ep, args.tp),
        role=args.role,
        kv_quant=getattr(args, "kv_quant", "none"),
        features=tuple(feats),
        hbm_per_chip_bytes=hbm,
        fabric=fabric)


def build_mesh(args):
    """Mesh from the parallelism flags (tp/dp/ep/sp/pp — MeshConfig's
    full axis set; ISSUE 9 satellite: sp-ring prefill and pp pipelines
    were dry-run-proven but unreachable from a real worker because only
    tp/dp/ep were read here).  Under multihost the degrees MUST span
    every process's chips — a prefix-sliced mesh that happens to fit
    one rank's devices would leave follower ranks shadowing computations
    on devices they can't address (and the lockstep channel pure
    overhead)."""
    sp = getattr(args, "sp", 1)
    pp = getattr(args, "pp", 1)
    if args.tp * args.dp * args.ep * sp * pp <= 1:
        if args.num_processes > 1:
            raise SystemExit(
                "--num-processes > 1 needs parallelism degrees that span "
                "the cluster (tp*dp*ep*sp*pp > 1); a meshless engine is "
                "process-local by construction")
        return None
    import jax

    from dynamo_tpu.parallel import MeshConfig, make_mesh

    mesh_cfg = MeshConfig(dp=args.dp, pp=pp, sp=sp, ep=args.ep,
                          tp=args.tp)
    devices = jax.devices()
    if mesh_cfg.size > len(devices):
        raise SystemExit(
            f"mesh {mesh_cfg.describe()} needs {mesh_cfg.size} devices; "
            f"{'the cluster' if args.num_processes > 1 else 'this host'} "
            f"has {len(devices)}")
    if mesh_cfg.size < len(devices):
        logger.warning(
            "mesh %s uses %d of %d devices; the rest idle "
            "(run more workers or raise --dp)",
            mesh_cfg.describe(), mesh_cfg.size, len(devices))
    mesh = make_mesh(mesh_cfg, devices[:mesh_cfg.size])
    if args.num_processes > 1:
        from dynamo_tpu.parallel.multihost import mesh_spans_processes

        if not mesh_spans_processes(mesh):
            raise SystemExit(
                f"mesh {mesh_cfg.describe()} fits rank 0's devices alone; "
                "multihost requires degrees that span all "
                f"{args.num_processes} processes' chips (raise --tp/--dp)")
    return mesh


def run_follower_rank(args) -> None:
    """Ranks 1..N-1 of a multihost worker: build the identical shadow
    EngineCore and replay the leader's lockstep command stream
    (parallel/multihost.py; the srun-rank analog)."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models.loader import resolve_model
    from dynamo_tpu.parallel.multihost import LockstepFollower, run_follower
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    if args.mocker:
        raise SystemExit("--mocker has no multihost mode (no device state "
                         "to span processes)")
    if not args.lockstep:
        raise SystemExit("follower ranks need --lockstep HOST:PORT")
    enable_compile_cache()
    cfg, params, _tok, _tpl = resolve_model(args.model or "llama-3-1b")
    if getattr(args, "moe_capacity", None) is not None:
        cfg = cfg.replace(moe_capacity=args.moe_capacity)
    core = EngineCore(
        EngineConfig(model=cfg,
                     num_blocks=args.num_blocks,
                     mesh=build_mesh(args),
                     dp_attention=args.dp_attention,
                     decode_window=args.decode_window,
                     # The shadow engine must derive the SAME compiled
                     # programs as the leader: cache mode and microbatch
                     # count are part of that identity (ISSUE 12 leg 4 —
                     # a follower without kv_quant would build a bf16
                     # cache and diverge on the first quantized step).
                     # MoE mode and capacity too (ISSUE 17): a follower
                     # resolving a different dispatch ladder rung would
                     # shadow a different compiled step.
                     moe_mode=getattr(args, "moe_mode", "auto"),
                     kv_quant=getattr(args, "kv_quant", "none"),
                     pp_microbatches=getattr(args, "pp_microbatches", 2),
                     scheduler=SchedulerConfig(
                         block_size=args.block_size,
                         max_pages_per_seq=-(-args.max_context
                                             // args.block_size),
                         max_prefill_chunk=args.max_prefill_chunk)),
        params=params)
    host, port = _split(args.lockstep)
    chan = LockstepFollower(host, port)
    print(f"worker rank {args.process_id}/{args.num_processes} following "
          f"lockstep at {args.lockstep}", flush=True)
    run_follower(core, chan)


async def build_engine(args, kv_event_sink):
    """Returns (engine_client, metrics_fn, shutdown, card_fields,
    transfer_engine) — transfer_engine serves the kv_blocks data plane
    (None for the mocker, which has no real KV bytes)."""
    if args.mocker:
        from dynamo_tpu.llm.mocker import MockEngine, MockEngineArgs

        engine = MockEngine(
            MockEngineArgs(block_size=args.block_size,
                           speedup_ratio=args.speedup_ratio),
            kv_event_sink=kv_event_sink)
        await engine.start()
        return engine, (lambda: engine.metrics), engine.stop, {}, None

    from dynamo_tpu.engine.engine import (
        EngineConfig, EngineCore, InferenceEngine)
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.llm.service import LocalEngineClient
    from dynamo_tpu.models.loader import resolve_model
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache
    from dynamo_tpu.runtime.program_store import open_store

    cache_dir = enable_compile_cache()
    logger.info("compile cache: %s", cache_dir)
    cfg, params, tok_spec, template = resolve_model(
        args.model or "llama-3-1b")
    if getattr(args, "moe_capacity", None) is not None:
        # Capacity is a model-level dispatch knob (ModelConfig) so every
        # compiled step sees it; the flag is the deployment's explicit
        # exactness/buffer-size trade (drops are counted, never silent).
        cfg = cfg.replace(moe_capacity=args.moe_capacity)
    mesh = build_mesh(args)
    core = EngineCore(
        EngineConfig(model=cfg,
                     num_blocks=args.num_blocks,
                     mesh=mesh,
                     program_store=open_store(cache_dir),
                     dp_attention=args.dp_attention,
                     decode_window=args.decode_window,
                     moe_mode=getattr(args, "moe_mode", "auto"),
                     kv_quant=getattr(args, "kv_quant", "none"),
                     pp_microbatches=getattr(args, "pp_microbatches", 2),
                     speculative_tokens=getattr(args, "spec_decode", 0),
                     speculative_ngram=getattr(args, "spec_ngram", 3),
                     packed_prefill={"auto": None, "on": True,
                                     "off": False}[
                         getattr(args, "packed_prefill", "auto")],
                     scheduler=SchedulerConfig(
                         block_size=args.block_size,
                         max_pages_per_seq=-(-args.max_context
                                             // args.block_size),
                         max_prefill_chunk=args.max_prefill_chunk)),
        params=params,
        kv_event_sink=kv_event_sink)
    if cfg.has_ssm and getattr(args, "role", "both") in ("prefill",
                                                           "decode"):
        from dynamo_tpu.engine.engine import STATE_NO_TRANSFER

        raise SystemExit(f"--role {args.role}: {STATE_NO_TRANSFER}")
    if cfg.has_window and getattr(args, "role", "both") in ("prefill",
                                                              "decode"):
        from dynamo_tpu.models.config import WINDOW_NO_TRANSFER

        raise SystemExit(f"--role {args.role}: {WINDOW_NO_TRANSFER}")
    if getattr(args, "prewarm_prefill", False):
        # Before the step-loop thread exists the constructing thread
        # owns the core, so the prewarm compiles run here and the first
        # request finds every packed shape in the jit cache.
        n_shapes = core.prewarm_prefill()
        print(f"prewarmed {n_shapes} packed prefill shapes", flush=True)
    core.join_read_ahead()
    engine = InferenceEngine(core)
    await engine.start()
    card_fields = {
        "tokenizer_spec": tok_spec,
        "chat_template": template,
        "max_context": cfg.max_context,
    }
    return LocalEngineClient(engine), (lambda: core.metrics), engine.stop, \
        card_fields, engine


async def run_encode(args, cp, runtime) -> None:
    """The encode-worker role: vision tower behind `encoder/encode` (no
    LLM engine, no model registration; reference
    `examples/multimodal_v1/components/encode_worker.py`)."""
    from dynamo_tpu.llm.multimodal import EncodeWorker, StubVisionEncoder
    from dynamo_tpu.models import config as mcfg

    try:
        hidden = mcfg.get_config(args.model or "llama-3-1b").hidden_size
    except Exception:
        hidden = 2048  # checkpoint-dir models: pass the preset via --model
    worker = EncodeWorker(StubVisionEncoder(hidden))
    endpoint = (runtime.namespace(args.namespace)
                .component("encoder").endpoint("encode"))
    instance = await endpoint.serve(worker.make_handler())
    print(f"encode worker instance {instance.instance_id} at "
          f"{instance.address} (hidden={hidden})", flush=True)
    stop_ev = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop_ev.set)
    await stop_ev.wait()
    await endpoint.leave()
    await runtime.shutdown()
    await cp.close()


async def run(args) -> None:
    from dynamo_tpu import native
    from dynamo_tpu.runtime import flight_recorder
    from dynamo_tpu.runtime.tracing import configure_from_args

    configure_from_args(args, service=f"worker-{args.component}")
    # Flight recorder: the worker's black box (ISSUE 14).  Configured
    # before anything serves so startup compiles/admissions land in the
    # ring; crash triggers (faulthandler, atexit, SIGUSR2) armed here on
    # the main thread.
    recorder = flight_recorder.configure_from_args(
        args, service=f"worker-{args.component}")
    recorder.install_crash_dump()
    # Device-truth plane (ISSUE 20): the XLA cost-analysis harvest must
    # be live BEFORE the engine builds — prewarmed prefill shapes and
    # startup compiles are first-seen exactly once and must land in the
    # program registry.  Captures write next to the flight dumps.
    from dynamo_tpu.runtime import compile_cache, device_profiler

    device_profiler.configure_from_args(
        args, service=f"worker-{args.component}")
    # Request ledger (ISSUE 18): hop ledgers only start when BOTH this
    # switch is on AND the incoming request carries the frontend's
    # ledger annotation.
    from dynamo_tpu.runtime import ledger as ledger_mod

    ledger_mod.configure_from_args(args)
    await native.warmup()  # build the C++ hasher off the event loop
    cp = ControlPlaneClient(*_split(args.control_plane))
    await cp.start()
    runtime = DistributedRuntime(cp, rpc_host=args.rpc_host)
    if args.role == "encode":
        await run_encode(args, cp, runtime)
        return
    # Prefill workers live under their own component so the frontend's
    # per-model clients (which watch the decode endpoint's instance
    # prefix) never route decode traffic to them — the reference's
    # separate prefill component (disagg_serving.md:62-64).
    component = (f"{args.component}-prefill" if args.role == "prefill"
                 else args.component)
    endpoint = (runtime.namespace(args.namespace)
                .component(component).endpoint(args.endpoint))

    loop = asyncio.get_running_loop()
    pending_events: list = []

    def kv_event_sink(event):
        # Engine threads may emit; hop onto the loop for the publish.
        loop.call_soon_threadsafe(pending_events.append, event)

    engine, metrics_fn, shutdown, card_fields, transfer_engine = \
        await build_engine(args, kv_event_sink)
    # Engine-thread stall watchdog (ISSUE 14): the step loop stamps a
    # heartbeat every iteration; no progress for --watchdog-stall-s
    # seconds while prefill/decode work is pending ⇒ stall event +
    # dynamo_engine_stalls_total + automatic flight-recorder dump.
    # Real engines only — the mocker has no step-loop heartbeat.
    watchdog = None
    if args.watchdog_stall_s > 0 and transfer_engine is not None:
        _wd_core = transfer_engine.core

        def _pending_work(core=_wd_core):
            # Off-thread read of live engine state; the watchdog treats
            # any exception here as "no pending work".
            return core.has_work

        watchdog = flight_recorder.StallWatchdog(
            recorder, _pending_work, stall_s=args.watchdog_stall_s)
        watchdog.start()
    lockstep = None
    if args.num_processes > 1:
        from dynamo_tpu.parallel.multihost import LockstepLeader

        if transfer_engine is None:
            raise SystemExit("--num-processes > 1 requires a real engine")
        port = (_split(args.lockstep)[1] if args.lockstep else 0)
        lockstep = LockstepLeader(port=port,
                                  num_followers=args.num_processes - 1)
        logger.info("multihost leader: lockstep on :%d, waiting for %d "
                    "follower(s)", lockstep.port, args.num_processes - 1)
        await asyncio.to_thread(lockstep.wait_for_followers)
        transfer_engine.core._lockstep = lockstep
    transfer_plane = None
    if transfer_engine is not None:
        from dynamo_tpu.llm.block_manager.transfer import (
            KV_BLOCKS_ENDPOINT, make_kv_blocks_handler)
        from dynamo_tpu.llm.discovery import (
            CLEAR_KV_ENDPOINT, EMBED_ENDPOINT, clear_kv_wire_handler,
            embed_wire_handler)

        runtime.rpc.register(KV_BLOCKS_ENDPOINT,
                             make_kv_blocks_handler(transfer_engine))
        runtime.rpc.register(EMBED_ENDPOINT, embed_wire_handler(engine))
        runtime.rpc.register(CLEAR_KV_ENDPOINT,
                             clear_kv_wire_handler(engine))
        if args.num_processes == 1:
            # Device-direct transfer plane (NIXL analog): blocks cross
            # worker↔worker device-to-device via PJRT's transfer service;
            # the host-staged kv_blocks plane stays as fallback.  Sharded
            # caches stage too: extract gathers the canonical block onto
            # device 0, the peer's inject scatters into ITS sharding —
            # so prefill tp=x → decode tp=y reshards in-flight (VERDICT
            # r4 next-5).  Multihost meshes stay host-staged (the plane
            # would need per-rank transfer servers).
            from dynamo_tpu.llm.block_manager.device_transfer import (
                KV_OFFER_ENDPOINT, KV_PULLED_ENDPOINT, KvTransferPlane)

            # ALWAYS started (ISSUE 16), so drain migration and prefix
            # pulls ride the device plane.  A transfer server that
            # cannot start ends the worker here, loudly.
            transfer_plane = KvTransferPlane(transfer_engine)
            taddr = transfer_plane.start()
            runtime.rpc.register(KV_OFFER_ENDPOINT,
                                 transfer_plane.make_offer_handler())
            runtime.rpc.register(KV_PULLED_ENDPOINT,
                                 transfer_plane.make_pulled_handler())
            logger.info("device transfer plane on %s (%s)", taddr,
                        transfer_plane.transport_kind)

    disagg_client = None
    prefill_task = None
    if args.role != "both" and transfer_engine is None:
        # The mocker has no real KV bytes to serve or pull — disagg roles
        # are meaningless for it.  Refuse loudly rather than serve
        # aggregated while the operator believes disagg is on.
        raise SystemExit(
            f"--role {args.role} requires a real engine (the mocker has "
            "no KV data plane); drop --role or --mocker")
    # Shared worker registry: request-lifecycle histograms (disagg KV
    # transfer, RPC-boundary TTFT/TPOT), the memory-plane KvCacheMetrics
    # family, and SLO burn-rate gauges.
    from dynamo_tpu.runtime.metrics import (
        HbmPoller, KvCacheMetrics, MetricsRegistry, RequestMetrics)
    from dynamo_tpu.runtime.slo import monitor_from_args

    registry = MetricsRegistry()
    request_metrics = RequestMetrics(registry)
    kv_metrics = KvCacheMetrics(registry)
    slo_monitor = monitor_from_args(args, request_metrics,
                                    registry=registry)
    if slo_monitor is not None:
        slo_monitor.start(interval=args.slo_tick)
    # Fleet-wide prefix reuse: consume router remote-prefix hints by
    # pulling the donor's sealed blocks over the kv_blocks plane before
    # engine admission (block_manager/prefix_share.py).  INNERMOST
    # wrapper — directly in front of the local engine — so on a
    # decode-role worker the pull runs AFTER any disagg remote-prefill
    # onboard: blocks the prefill worker already delivered are locally
    # resident by then and the fetcher's residency check skips the wire
    # entirely, while a failed/local-prefill path still benefits from
    # the donor's blocks.  Every real engine also SERVES kv_blocks
    # above, so any worker is a donor.
    prefix_fetcher = None
    serve_base = engine
    if transfer_engine is not None and not args.no_prefix_share:
        from dynamo_tpu.llm.block_manager.prefix_share import (
            PrefixFetcher, PrefixShareClient)

        prefix_fetcher = PrefixFetcher(
            transfer_engine, runtime.client_for, args.block_size,
            plane=transfer_plane)
        serve_base = PrefixShareClient(engine, prefix_fetcher)

    if args.role == "decode":
        from dynamo_tpu.llm.disagg import DisaggDecodeClient, disagg_config_key

        if args.max_local_prefill is not None:
            await cp.put(disagg_config_key(args.namespace),
                         {"max_local_prefill_length": args.max_local_prefill})
        disagg_client = DisaggDecodeClient(
            serve_base, transfer_engine, cp, args.namespace, args.block_size,
            transfer_plane=transfer_plane, request_metrics=request_metrics,
            eager=not args.no_eager_kv)
        await disagg_client.start()
        serve_client = disagg_client
    else:
        serve_client = serve_base

    # SLO-aware tier demotion: while the error budget burns, hot prefix
    # blocks resist device→host→disk demotion (pool.slo_eviction_bias
    # over the monitor's cheap last_max_burn attribute).
    if slo_monitor is not None and transfer_engine is not None:
        manager = getattr(transfer_engine.core.allocator, "manager", None)
        if manager is not None:
            from dynamo_tpu.llm.block_manager.pool import slo_eviction_bias

            manager.set_eviction_bias(slo_eviction_bias(
                lambda: slo_monitor.last_max_burn))
        # QoS preemption lever (ISSUE 15 leg 3): burn >= 1 holds
        # best-effort admissions and sheds running best-effort requests
        # (their KV demotes to the host tier; resume = tier onboard).
        # NOT under multihost lockstep: follower shadow schedulers never
        # see the leader's host-local burn signal, and a pressure-driven
        # preempt only on rank 0 would diverge the SPMD batch shapes.
        if args.num_processes == 1:
            transfer_engine.core.scheduler.qos_pressure_fn = (
                lambda: slo_monitor.last_max_burn)

    # Drain wrapper (ISSUE 15): OUTERMOST serving stage so a drain
    # cancels the whole disagg/prefix-share/engine chain beneath it and
    # ends each wire stream with the KV-carrying migrate delta.
    from dynamo_tpu.llm.drain import (
        DRAIN_PREFIX, DrainableService, drain_key_instance, drain_key_pid)

    drainable = DrainableService(serve_client,
                                 block_size=args.block_size)
    # Published slice topology (ISSUE 16): the instance record carries
    # this worker's SliceSpec so the fleet brain — KvRouter donor picks,
    # QoS selector HBM scaling, planner placement — reasons about mesh
    # shape, role, kv mode and transfer-plane reachability WITHOUT any
    # new scrape path.
    slice_spec = derive_slice_spec(
        args, fabric=transfer_plane.fabric if transfer_plane else "")
    instance = await endpoint.serve(
        engine_wire_handler(drainable, request_metrics=request_metrics),
        metadata={"slice": slice_spec.to_dict()})
    if transfer_engine is not None and not getattr(
            getattr(transfer_engine, "core", None), "_ssm", False) \
            and not getattr(getattr(transfer_engine, "core", None),
                            "_window", False):
        # Peers pull the handed-off KV from this worker's kv_blocks
        # endpoint — the instance address IS the donor descriptor.  (A
        # model with state-space layers hands its streams off without one:
        # its blocks carry no recurrent state, so the peer prefills again;
        # engine.STATE_NO_TRANSFER.)
        drainable.kv_address = instance.address
    # (Transfer-plane discovery needs no control-plane record: the peer's
    # RPC address is already the instance record, and the per-transfer
    # descriptor — uuid + transfer address — travels in the kv_offer
    # reply, the NIXL-metadata analog.)
    if args.role == "prefill":
        # Prefill workers serve the queue, not the routed model: no
        # register_llm, so frontends never route decode traffic here
        # (reference prefill workers register under their own component,
        # disagg_serving.md:62-64).
        from dynamo_tpu.llm.disagg import prefill_worker_loop

        prefill_task = asyncio.create_task(prefill_worker_loop(
            cp, args.namespace, engine, instance.address))
    else:
        card = ModelDeploymentCard(name=args.model_name,
                                   kv_block_size=args.block_size,
                                   **card_fields)
        await register_llm(endpoint, instance, card)
    status = None
    hbm_poller = None
    status_reg_task = None
    if args.health_port >= 0:
        from dynamo_tpu.runtime.status import (
            StatusServer, register_status_endpoint_task)

        @never_engine_thread
        def worker_metrics_text() -> str:
            m = metrics_fn()
            ws, ks = m.worker_stats, m.kv_stats
            lines = [
                f"dynamo_worker_request_active_slots {ws.request_active_slots}",
                f"dynamo_worker_requests_waiting {ws.num_requests_waiting}",
                f"dynamo_worker_kv_active_blocks {ks.kv_active_blocks}",
                f"dynamo_worker_kv_usage {ks.gpu_cache_usage_perc}",
                "dynamo_worker_kv_prefix_cache_hit_rate "
                f"{ks.gpu_prefix_cache_hit_rate}",
            ]
            if m.expert_load:
                # MoE telemetry (ISSUE 17): per-expert assignment
                # distribution plus the capacity-honesty drop counter
                # (0 forever at the exact-capacity serving default).
                for e, n in enumerate(m.expert_load):
                    lines.append(
                        f'dynamo_moe_expert_load{{expert="{e}"}} {n}')
                lines.append("dynamo_moe_dropped_tokens_total "
                             f"{m.moe_dropped_tokens}")
            # Serving-loop overhead counters (EngineStepCounters) —
            # host syncs / compiled-shape cache misses per dispatch
            # class; mocker-backed workers have no core and skip this.
            core = getattr(getattr(engine, "_engine", None), "core", None)
            counters = getattr(core, "counters", None)
            if counters is not None:
                for k, v in counters.to_dict().items():
                    lines.append(f"dynamo_worker_engine_{k} {v}")
                # Where the engine thread's wall time went, by phase.
                lines.extend(counters.phase_metrics_lines())
                # Where each request's seconds went, by state, and the
                # share of the window path's seconds that chunks took.
                lines.extend(counters.request_state_metrics_lines())
                lines.extend(counters.block_metrics_lines())
            # What building programs cost (jax.monitoring, summed since
            # enable_compile_cache(); nothing on a mocker).
            lines.extend(compile_cache.metrics_lines())
            # Flight-recorder / stall-watchdog series (ISSUE 14): the
            # step-loop heartbeat age feeds `dynamo top`'s AGE/STL
            # column; the stall counter is the chaos-era "worker wedged
            # under load" alarm.
            age = recorder.last_step_age_s()
            if age is not None:
                lines.append(
                    f"dynamo_engine_last_step_age_seconds {age:.3f}")
            lines.append(f"dynamo_engine_stalls_total {recorder.stalls}")
            lines.append("dynamo_engine_stalled "
                         f"{1 if watchdog is not None and watchdog.stalled else 0}")
            # Elasticity / QoS plane (ISSUE 15): feeds `dynamo top`'s
            # QOS/DRN column and the chaos-test oracles.
            lines.append("dynamo_requests_migrated_total "
                         f"{drainable.migrated_out}")
            lines.append("dynamo_worker_draining "
                         f"{1 if drainable.draining else 0}")
            if core is not None:
                lines.append("dynamo_qos_preemptions_total "
                             f"{core.scheduler.qos_preemptions}")
                lines.append("dynamo_qos_demoted_blocks_total "
                             f"{core.qos_demoted_blocks}")
            if prefix_fetcher is not None:
                lines.append("dynamo_requests_migrated_in_total "
                             f"{prefix_fetcher.migrated_in}")
            # Memory-plane sample at scrape time: pool occupancy /
            # eviction / prefix-hit series land in the shared registry.
            # Runs on the status server's event loop (host ints only),
            # never the engine thread.
            if core is not None:
                kv_metrics.observe_engine(core)
            if prefix_fetcher is not None:
                kv_metrics.observe_prefix_share(prefix_fetcher)
            # Plane-choice tallies (device vs host, with fallback
            # reasons): a fleet silently degraded to host staging shows
            # up here and in `dynamo top`'s PLANE column.
            kv_metrics.observe_transfer_plane()
            # Device-truth plane (ISSUE 20): fold modeled counters
            # against the XLA cost registry at scrape time (host floats
            # only — the engine thread never participates), then export
            # the program registry + drift ratios.
            prof = device_profiler.get_profiler()
            if prof.enabled:
                if core is not None:
                    prof.audit_engine(core)
                lines.extend(prof.metrics_lines())
            return "\n".join(lines) + "\n"

        status = StatusServer(
            registry=registry, extra_text_fn=worker_metrics_text,
            slo_fn=(slo_monitor.payload if slo_monitor is not None
                    else None))
        hport = await status.start(host=args.rpc_host,
                                   port=args.health_port)
        # Advertise for fleet discovery: metrics_aggregator scrapes it,
        # `dynamo top` renders it.  Best-effort with retry — a control
        # plane mid-restart must not crash the worker.
        status_reg_task = register_status_endpoint_task(
            cp, f"worker-{args.role}", hport, host=args.rpc_host,
            extra={"mesh": slice_spec.describe(),
                   "slice": slice_spec.to_dict()})
        if args.hbm_poll_interval > 0 and transfer_engine is not None:
            hbm_poller = HbmPoller(kv_metrics,
                                   interval=args.hbm_poll_interval)
            hbm_poller.start()
        print(f"worker status server on :{hport}", flush=True)
    print(f"worker instance {instance.instance_id} role={args.role} "
          f"serving {args.model_name!r} at {instance.address}", flush=True)

    async def pump_events():
        while True:
            await asyncio.sleep(0.02)
            while pending_events:
                ev = pending_events.pop(0)
                await cp.publish(KV_EVENTS_SUBJECT, RouterEvent(
                    worker_id=instance.instance_id, event=ev).to_dict())

    async def pump_metrics():
        while True:
            await asyncio.sleep(args.metrics_interval)
            m = metrics_fn()
            await cp.publish(METRICS_SUBJECT, {
                "worker_id": instance.instance_id,
                "metrics": m.to_dict()})

    pumps = [asyncio.create_task(pump_events()),
             asyncio.create_task(pump_metrics())]

    stop_ev = asyncio.Event()
    drain_started = [False]

    async def start_drain(reason: str) -> None:
        """Planned drain (ISSUE 15): leave routing, hand every in-flight
        stream to a peer with its KV, linger for the peers' pulls, then
        let the normal shutdown path run.  Idempotent — a SIGTERM racing
        a control-plane drain command drains once."""
        if drain_started[0]:
            return
        drain_started[0] = True
        try:
            logger.warning("drain (%s): leaving routing, handing off %d "
                           "in-flight stream(s)", reason,
                           drainable.active_requests)
            await endpoint.leave()      # instant removal from routing
            await drainable.drain(args.drain_timeout_s)
            if drainable.migrated_out and transfer_engine is not None:
                # Handed-off KV only moves if the peers' kv_blocks pulls
                # get to run: give them a beat to open their streams,
                # then wait (bounded) until the RPC plane goes quiet.
                await asyncio.sleep(max(0.0, args.drain_linger_s))
                deadline = loop.time() + max(0.0, args.drain_timeout_s)
                while runtime.rpc.active_streams > 0 \
                        and loop.time() < deadline:
                    await asyncio.sleep(0.05)
            logger.info("drain complete: %d stream(s) migrated out",
                        drainable.migrated_out)
        except Exception:
            # A drain that trips over a dead control plane must still
            # END the worker — a latched drain_started with no stop_ev
            # would make every later SIGTERM inert until the connector
            # escalates to SIGKILL (dropping the KV this path exists to
            # save).
            logger.exception("drain (%s) failed; shutting down anyway",
                             reason)
        finally:
            stop_ev.set()

    def on_sigterm():
        if args.drain == "off":
            stop_ev.set()
        else:
            asyncio.ensure_future(start_drain("sigterm"))

    loop.add_signal_handler(signal.SIGINT, stop_ev.set)
    loop.add_signal_handler(signal.SIGTERM, on_sigterm)

    async def watch_drain_commands():
        """The control-plane `drain` command: a put under drain/<pid> or
        drain/instance/<id> drains this worker exactly like SIGTERM —
        the operator/planner surface for boxes where signals don't reach
        (containers, remote hosts)."""
        import os as _os

        mine = {drain_key_pid(_os.getpid()),
                drain_key_instance(instance.instance_id)}
        try:
            watch = await cp.watch_prefix(DRAIN_PREFIX)
            async for ev in watch:
                if ev.kind == "put" and ev.key in mine:
                    logger.warning("control-plane drain command: %s",
                                   ev.key)
                    await start_drain("control_plane")
                    return
        except (ConnectionError, asyncio.CancelledError):
            return  # cp gone / shutdown: the SIGTERM path still drains

    drain_watch = (asyncio.create_task(watch_drain_commands())
                   if args.drain != "off" else None)

    async def watch_profile_commands():
        """The control-plane `profile` command: a put under
        profile/<pid> or profile/instance/<id> runs one bounded device
        capture on this worker (value: capture ms, default 500, then
        optionally the word `python` for Python frames) — the
        operator surface for boxes where /debug/deviceprofile isn't
        reachable.  Loops: one worker serves many captures."""
        import os as _os

        from dynamo_tpu.runtime.device_profiler import (
            PROFILE_PREFIX, parse_profile_command, profile_key_instance,
            profile_key_pid)

        mine = {profile_key_pid(_os.getpid()),
                profile_key_instance(instance.instance_id)}
        prof = device_profiler.get_profiler()
        try:
            watch = await cp.watch_prefix(PROFILE_PREFIX)
            async for ev in watch:
                if ev.kind != "put" or ev.key not in mine:
                    continue
                ms, python = parse_profile_command(ev.value)
                logger.warning("control-plane profile command: %s "
                               "(%d ms%s)", ev.key, ms,
                               ", python frames" if python else "")
                # to_thread: the capture sleeps for its bound; the
                # worker's event loop must keep serving under it.
                res = await asyncio.to_thread(prof.capture, ms, python)
                logger.warning("device capture result: %s",
                               {k: res.get(k)
                                for k in ("ok", "dir", "error")})
        except (ConnectionError, asyncio.CancelledError):
            return  # cp gone / shutdown: /debug/deviceprofile remains

    profile_watch = (asyncio.create_task(watch_profile_commands())
                     if device_profiler.get_profiler().enabled else None)
    await stop_ev.wait()

    # Graceful drain: leave routing instantly, finish in-flight streams
    # (already done — and bounded — when start_drain ran).
    if drain_watch is not None:
        drain_watch.cancel()
    if profile_watch is not None:
        profile_watch.cancel()
    await endpoint.leave()
    stream_deadline = loop.time() + max(5.0, args.drain_timeout_s)
    while runtime.rpc.active_streams > 0 and loop.time() < stream_deadline:
        await asyncio.sleep(0.05)
    for t in pumps:
        t.cancel()
    if prefill_task:
        prefill_task.cancel()
    if disagg_client is not None:
        await disagg_client.stop()
    if status_reg_task is not None:
        status_reg_task.cancel()
    if watchdog is not None:
        watchdog.stop()
    if hbm_poller is not None:
        hbm_poller.stop()
    if slo_monitor is not None:
        await slo_monitor.stop()
    if status is not None:
        await status.stop()
    await shutdown()
    if lockstep is not None:
        lockstep.close()  # broadcasts "stop"; follower ranks exit
    await runtime.shutdown()
    await cp.close()


def _split(addr: str):
    host, port = addr.rsplit(":", 1)
    return host, int(port)


def main(argv=None) -> None:
    logging.basicConfig(level=logging.INFO)
    args = parse_args(argv)
    if args.num_processes > 1 and args.process_id > 0:
        run_follower_rank(args)   # ranks 1..N-1: shadow engine, no serving
        return
    asyncio.run(run(args))


if __name__ == "__main__":
    main()
