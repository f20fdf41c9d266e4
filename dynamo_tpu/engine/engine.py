"""The inference engine: device state + step loop + async streaming.

Replaces the reference's delegated engines (vLLM `AsyncLLM` wrapped at
`components/backends/vllm/src/dynamo/vllm/main.py:116`) with our own:

- `EngineCore` — synchronous: owns params, the paged cache, the compiled
  step per (batch/chunk) bucket, and the scheduler; `step()` runs one
  engine iteration and returns per-request deltas.  Deviceless tests can
  drive it directly on CPU.
- `InferenceEngine` — the async facade workers serve: `generate()` yields
  token deltas as an async stream (the `AsyncEngine.generate →
  ManyOut<Resp>` contract, reference `lib/runtime/src/engine.rs:207`),
  running the core loop in a dedicated thread so device blocking never
  stalls the event loop.

KV events: page completions emit chained-hash STORED events and frees emit
REMOVED events through a pluggable publisher — the same event stream the
reference's vLLM worker bridges over ZMQ (`kv_router/publisher.rs:222`),
here born native.

Padding discipline (see scheduler.py): block tables are sliced to the
batch's page bucket (context-length bucketing — the gather cost scales
with actual context, not max_context), unallocated entries are the null
block 0, and all padding writes target position `max_pages * block_size`,
which indexes past every runtime table width and resolves to the null
block — padded lanes can never corrupt live cache pages.
"""

from __future__ import annotations

import asyncio
import functools
import json
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import AsyncIterator, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.sampling import SamplingParams, chosen_logprobs, sample
from dynamo_tpu.engine.sampling import greedy as greedy_sample
from dynamo_tpu.engine.scheduler import (
    DECODE_SHARE,
    BlockAllocator,
    DecodeWork,
    FinishReason,
    PrefillBatch,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
)
from dynamo_tpu.llm.kv_router.protocols import (
    ForwardPassMetrics,
    KvCacheEvent,
    KvCacheEventData,
    KvStats,
    SpecDecodeStats,
    WorkerStats,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import Params, init_params, make_forward_step
from dynamo_tpu.runtime import contracts, device_profiler, flight_recorder
from dynamo_tpu.runtime import program_store
from dynamo_tpu.runtime import ledger as request_ledger
from dynamo_tpu.runtime.contracts import (
    engine_thread_only,
    hot_path,
    never_engine_thread,
)
from dynamo_tpu.runtime.metrics import (
    CHANCE_DISPATCHED,
    CHANCE_DUTY_SKIPPED,
    CHANCE_NO_BUDGET,
    CHANCE_NO_WINDOW,
    JOIN_AT_CHUNK,
    JOIN_AT_SETTLE,
    PHASE_COMMANDS,
    PHASE_DELIVER,
    PHASE_DISPATCH_BLOCK,
    PHASE_DISPATCH_PREFILL,
    PHASE_DISPATCH_WINDOW,
    PHASE_EMIT,
    PHASE_IDLE,
    PHASE_PLAN,
    PHASE_SETTLE_FIRST,
    PHASE_SINGLE_STEP,
    PHASE_WAIT_DEVICE,
    RS_BUDGET_WAIT,
    RS_COHORT_WAIT,
    RS_DECODE,
    RS_FIRST_TOKEN,
    RS_PREEMPTED,
    RS_PREFILL,
    RS_WAITING,
)
from dynamo_tpu.tokens import TokenBlockSequence
from dynamo_tpu.parallel.sharding import (
    PlaneSpec,
    cache_pspecs,
    check_plane,
    init_on_mesh,
    make_sharded_step,
    param_pspecs,
    plane_capability,
    shard_pytree,
)

logger = logging.getLogger(__name__)


@dataclass
class TokenDelta:
    """One engine-step output for one request."""

    request_id: str
    token_ids: List[int]
    finished: bool = False
    finish_reason: Optional[FinishReason] = None
    # log p(token) per entry of token_ids; only populated for requests
    # with sampling.logprobs set.
    logprobs: Optional[List[float]] = None
    # Drain handoff (llm/drain.py): a worker leaving the fleet ends the
    # stream with this set instead of a finish — {"reason", "covered_tokens",
    # "address"?} tells the frontend's MigrationClient to resume the
    # stream on a peer, pulling the resident KV from `address` first.
    # Never reaches end clients; the migration layer consumes it.
    migrate: Optional[dict] = None
    # Request-ledger return leg (runtime/ledger.py): a worker hop's
    # completed phase-stamp wire dict, attached by engine_wire_handler
    # to the final (or migrate) delta and absorbed into the frontend's
    # live ledger.  Same tolerance contract as `migrate`: old frontends
    # never read it, old workers never set it, garbage is dropped with a
    # rate-limited warn and never fails the request.
    ledger: Optional[dict] = None
    # The engine's own intervals of this request, from the request-state
    # clock's stamps (EngineCore._first_token_timings): on the delta that
    # carries its first token the instants of arrival, admission, first
    # chunk planned, prefill done and first token; on its last delta the
    # seconds in `cohort_wait` and `preempted`.  In-process only (the
    # serving layer stamps the request ledger from it on its event loop
    # and the wire codec leaves it out); None with the ledger off.
    timings: Optional[dict] = None


@dataclass(frozen=True)
class EngineConfig:
    model: ModelConfig
    num_blocks: int = 512
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    cache_dtype: Optional[jnp.dtype] = None
    # Quantized KV plane (`--kv-quant`): "int8" stores K/V pages as int8
    # with per-token-per-head f32 scales and dequantizes inside the
    # decode kernel's VMEM tile — HBM bytes per context token drop to
    # ~0.53x bf16 at serving geometry (kv_cache.py module docstring).
    # Composes with EVERY mesh (ISSUE 12): tp/dp/dp_attention/dp-local
    # (scales shard with their kv heads / slots), ring-SP (the chunk
    # exchange rotates int8 rows + scales), pp (stacked scale buffers)
    # and multi-process lockstep meshes; any future impossible combo is
    # declared in parallel.sharding.plane_capability, not here.
    kv_quant: str = "none"
    # MoE compute mode (parallel/sharding.resolve_moe_mode): "auto" picks
    # the grouped Pallas fast path on meshless TPU engines (eligible
    # geometry), all-to-all dispatch on ep > 1 meshes, dense otherwise.
    # Explicit "dense" | "grouped" | "dispatch" pin a rung; invalid
    # combos (grouped × mesh, dispatch × meshless) raise pointedly.
    moe_mode: str = "auto"
    mesh: Optional[object] = None          # jax.sharding.Mesh for tp/ep
    # Batch-sharded attention with slot-sharded KV (tp beyond the kv-head
    # count; reference sglang --enable-dp-attention).
    dp_attention: bool = False
    # dp-attention page LOCALITY (VERDICT r3 weak #4): cache slots shard
    # over the flat (dp, tp) grid, decode rows pin to their slot, and the
    # sharded allocator keeps each row's pages on its own device — decode
    # attention then runs shard-locally (no cross-chip gathers).  None =
    # auto: on when dp_attention runs under a mesh with the plain
    # allocator (the tiered prefix cache has no shard concept yet).
    dp_attention_local: Optional[bool] = None
    seed: int = 0
    enable_kv_events: bool = True
    # Prefix cache / tiered KVBM (G1 device always; G2 host / G3 disk when
    # sized > 0).  Off → plain free-list allocator, no reuse.
    enable_prefix_cache: bool = True
    host_blocks: int = 0
    disk_blocks: int = 0
    # G4 remote tier: `remote_fetch_fn(block_hash) -> Optional[ndarray]`,
    # consulted on local-tier misses during admission matching.  Must be
    # synchronous and bounded (runs on the engine thread).
    remote_fetch_fn: Optional[Callable] = None
    # Pallas paged-decode kernel; None = auto (TPU backend, unsharded —
    # the sharded step keeps the GSPMD-partitionable gather path).
    use_pallas_decode: Optional[bool] = None
    # Packed ragged prefill plane (ISSUE 10): scheduled prefill chunks
    # pack into ONE flat token axis with per-segment block tables and
    # attention streams pages from the pool via the Pallas flash-prefill
    # kernel (ops/pallas/paged_prefill.py) — no [R, T] bucket padding,
    # no gather_kv materialisation, and a shape lattice small enough to
    # prewarm (the cold-prefill cliff).  None = auto: on for TPU,
    # meshless engines whose geometry passes mosaic_geometry_ok (the
    # decode kernel's shared eligibility rule); everything else keeps
    # the padded gather plane.  MoE composes (ISSUE 17): the packed
    # hidden rides _moe_block with the engine's meshless moe_mode.
    # Explicit True off TPU runs the kernel in interpret mode (tests).
    packed_prefill: Optional[bool] = None
    # Fused decode window: K tokens per device dispatch with on-device
    # token feedback, host syncs lagging `pipeline_depth` windows behind.
    # 1 disables (single-step host loop).  Eliminates the per-token host
    # sync (SURVEY §7 decode hard part).  The device→host sync itself is
    # ASYNC: the token block's copy starts at dispatch time on a fetch
    # thread, so syncs cost ~0 while pipeline_depth × window × step_time
    # exceeds the copy's latency.  (The depth was sized for a host↔device
    # latency the attached chip does not have: ROADMAP queue item.)
    decode_window: int = 8
    window_pipeline_depth: int = 8
    # Self-speculative decoding (`--spec-decode`): when > 0, decode steps
    # draft `speculative_tokens` continuation tokens (prompt-lookup
    # n-gram by default; `drafter` plugs in anything, e.g. a draft
    # model), verify them in ONE batched forward through the existing
    # step, and accept the longest agreeing prefix — greedy rows emit
    # the exact argmax chain (byte-identical to non-spec greedy);
    # stochastic rows use rejection-sampling fallback
    # (sampling.speculative_verify), so the output DISTRIBUTION is
    # unchanged.  Repetitive text (code, extraction, RAG quotes, agent
    # loops) accepts multiple tokens per step, amortising each
    # bandwidth-bound HBM sweep over >1 emitted token.
    speculative_tokens: int = 0
    speculative_ngram: int = 3
    # Pluggable draft proposer (engine/drafter.py Drafter); None = the
    # NgramDrafter(speculative_ngram) prompt-lookup default.
    drafter: Optional[object] = None
    # Sequence-parallel ring prefill (mesh with sp > 1): full-prompt
    # prefills of at least this many tokens route through the ICI ring
    # (ops/ring_attention.py) instead of the chunked gather path — the
    # long-context serving path (SURVEY §2.5 SP row).
    sp_prefill_threshold: int = 256
    # Pipeline parallelism (mesh with pp > 1): GPipe microbatch count for
    # the stage-rotated step (parallel/pipeline.py).
    pp_microbatches: int = 2
    # `runtime.program_store.ProgramStore` of the serving entry points:
    # the meshless step programs then start from stored executables
    # instead of a trace and a lowering each.  None: plain `jax.jit`.
    program_store: Optional[object] = None


# What the block transfer plane says when asked for the blocks of a model with
# state-space layers (disaggregated prefill/decode, drain migration, fleet
# prefix pulls all move a sequence by its blocks).
STATE_NO_TRANSFER = (
    "a model with state-space layers does not move a sequence by its KV "
    "blocks: disaggregated transfer, drain migration and tier offload would "
    "leave its recurrent state behind (no state snapshot exists)")


class EngineCore:
    """Synchronous engine: one `step()` = one scheduler plan executed."""

    def __init__(
        self,
        config: EngineConfig,
        params: Optional[Params] = None,
        kv_event_sink: Optional[Callable[[KvCacheEvent], None]] = None,
    ) -> None:
        self.config = config
        cfg = config.model
        sched_cfg = config.scheduler
        # Block-diffusion model: a decode step decides a block of B
        # positions a sequence (see _run_block_decode).  Everything the
        # scheduler has to know of it is the block length.
        self._diffusion = cfg.is_diffusion
        if self._diffusion:
            import dataclasses as _dc

            if config.mesh is not None or config.speculative_tokens:
                raise ValueError(
                    "a block-diffusion model serves meshless and without "
                    "speculative decoding (its block step has no sharded "
                    "variant, and a block is its own draft)")
            sched_cfg = _dc.replace(
                sched_cfg, token_block=cfg.diffusion_block_length)
        # A model with window layers keeps a second page group (a pool and a
        # table a sequence) for them.  What has no form for it is refused
        # here by name: a mesh, speculative decoding, the tiers below the
        # device; int8 pages by KvCacheConfig, block diffusion and latent
        # attention by ModelConfig.validate, the ring path by
        # make_forward_step, block export and import (disaggregated
        # transfer, drain migration) below (`WINDOW_NO_TRANSFER`).
        self._window = cfg.has_window
        if self._window:
            from dynamo_tpu.models.config import (
                WINDOW_MESHLESS, WINDOW_NO_SPECULATION, WINDOW_NO_TRANSFER)

            if config.mesh is not None:
                raise ValueError(WINDOW_MESHLESS)
            if config.speculative_tokens:
                raise ValueError(WINDOW_NO_SPECULATION)
            if config.host_blocks or config.disk_blocks \
                    or config.remote_fetch_fn is not None:
                raise ValueError(WINDOW_NO_TRANSFER)
        # What follows from the model's configuration and has one form
        # only: the latent (MLA) cache, and the DeepSeek-V3 expert layer
        # (sigmoid router, shared expert, leading dense layers), serve
        # meshless.  Refused here by name, as a block program is under a
        # mesh; an int8 latent cache is refused by KvCacheConfig, a
        # block-diffusion latent model by ModelConfig.validate.
        if config.mesh is not None and cfg.is_latent:
            from dynamo_tpu.models.llama import LATENT_MESHLESS

            raise ValueError(LATENT_MESHLESS)
        if config.mesh is not None and (
                cfg.n_shared_experts or cfg.first_k_dense
                or cfg.router_scoring != "softmax"):
            raise ValueError(
                "an expert layer with a shared expert, a sigmoid router or "
                "leading dense layers serves meshless: the sharded expert "
                "paths (GSPMD dense, ep dispatch) have no form for them")
        # A model with state-space layers keeps a slot of recurrent state a
        # sequence beside the pages.  What has no form for that state is
        # refused here by name: a mesh, speculative decoding
        # (a rejected draft would have advanced the state), the tiers below
        # the device (an offloaded or fetched block says nothing of the
        # state at its end).  int8 pages are refused by KvCacheConfig, block
        # diffusion by ModelConfig.validate, the ring path by
        # make_forward_step, block export and import below
        # (`STATE_NO_TRANSFER`).
        self._ssm = cfg.has_ssm
        if self._ssm:
            from dynamo_tpu.models.config import STATE_MESHLESS

            if config.mesh is not None:
                raise ValueError(STATE_MESHLESS)
            if config.speculative_tokens:
                raise ValueError(
                    "a model with state-space layers serves without "
                    "speculative decoding: a rejected draft token would "
                    "have advanced the recurrent state")
            if config.host_blocks or config.disk_blocks \
                    or config.remote_fetch_fn is not None:
                raise ValueError(
                    "a model with state-space layers has no tier offload: "
                    "host_blocks, disk_blocks and remote_fetch_fn are "
                    "refused (a block below the device carries no "
                    "recurrent state)")
        # Layers by a pattern: a padding row of a decode step costs what a
        # live one does (42 MB of state in and out and its routed experts at
        # the published widths of the one such model served), and at the
        # rows a one-chip share of it decodes the power-of-two ladder's 16
        # -> 32 edge is a quarter of a step's time, so arrival order alone
        # moved a run's time per token by 12 % (PERF.md section 6, PR 47).
        # Falcon-H1's rows cost the same way; its accepted cell is held to
        # its set-up time and its ladder is a perf_opt PR's to change.
        if cfg.has_pattern:
            sched_cfg = sched_cfg.with_decode_rows_every(8)
        self.block_size = sched_cfg.block_size
        window_blocks = 0
        if self._window:
            # The window group's pool, sized on its own: what every
            # sequence the scheduler can hold needs at most (a window and a
            # chunk each), but no more bytes than the full group's pool has
            # (`num_blocks` blocks of the full layers): admission counts
            # both groups.
            from dynamo_tpu.engine.scheduler import window_cap_blocks

            n_window = len(cfg.window_layers)
            window_blocks = 1 + min(
                sched_cfg.max_seqs * window_cap_blocks(
                    cfg.max_window, sched_cfg.max_prefill_chunk,
                    self.block_size),
                max(config.num_blocks
                    * (len(cfg.attention_layers) - n_window) // n_window, 1))
        self.cache_cfg = kvc.KvCacheConfig.for_model(
            cfg, num_blocks=config.num_blocks, block_size=self.block_size,
            dtype=config.cache_dtype, kv_quant=config.kv_quant,
            state_slots=sched_cfg.max_seqs, window_blocks=window_blocks,
        )
        self.mesh = config.mesh
        # Multi-process mesh (SURVEY §2.5 multinode analog): every process
        # runs this same EngineCore in SPMD lockstep — process 0 leads
        # (scheduler + serving), followers replay its command stream
        # (parallel/multihost.py).  Host→device inputs then ride
        # make_array_from_callback (via sharding._finalize wrappers) and
        # host reads come off replicated outputs.
        self._mh = False
        if self.mesh is not None:
            from dynamo_tpu.parallel.multihost import mesh_spans_processes

            self._mh = mesh_spans_processes(self.mesh)
        # Feature × mesh composition (ISSUE 12): the capability table in
        # parallel/sharding.py is THE one place declaring impossible
        # combos — int8 now composes with pp (stacked scale buffers),
        # ring-SP (quantized chunk exchange) and the lockstep stream
        # (the packed wire block and shard_pytree are layout-agnostic),
        # so the old hand-maintained rejection list here is gone.
        # Speculative decode is gated at CONSTRUCTION so an incapable
        # combo fails pointedly instead of silently never drafting.
        if self.mesh is not None:
            # (dp_local is granted permissively here — its precise
            # resolution happens below and make_sharded_step re-checks
            # the resolved plane, so pallas × NON-local dp_attention
            # still raises at construction with the table's reason.)
            check_plane(
                self.mesh,
                PlaneSpec(quant=self.cache_cfg.quantized,
                          spec=config.speculative_tokens > 0,
                          use_pallas=config.use_pallas_decode is True,
                          dp_attention=config.dp_attention,
                          dp_local=config.dp_attention,
                          moe=cfg.is_moe),
                multihost=self._mh)
        # Host-side staging for device inputs: single-process uploads
        # eagerly (and caches what stays constant on the device);
        # multihost keeps numpy and lets the step wrappers build global
        # arrays per call (per-step data changes anyway).
        self._dev = (lambda x: x) if self._mh else jnp.asarray
        # Per-request-set-CONSTANT window state must not re-convert every
        # dispatch (the same reason the single-process path caches device
        # arrays): multihost converts ONCE to a global array with the
        # batch sharding; the step wrapper then passes it through.
        if self._mh:
            from jax.sharding import NamedSharding, PartitionSpec

            from dynamo_tpu.parallel.multihost import to_global

            _axes = (("dp", "tp") if config.dp_attention else "dp")

            def _dev_row(x, _s=NamedSharding(self.mesh,
                                             PartitionSpec(_axes))):
                return to_global(x, _s)

            def _dev_row2(x, _s=NamedSharding(self.mesh,
                                              PartitionSpec(_axes, None))):
                return to_global(x, _s)

            self._dev_row, self._dev_row2 = _dev_row, _dev_row2
        else:
            self._dev_row = self._dev_row2 = jnp.asarray
        # Lockstep broadcast channel (leader only; followers and
        # single-process engines leave it None).
        self._lockstep = None

        # Random-init engines build params (and every engine its cache)
        # through ONE jitted initialiser: meshless on the default device,
        # under a mesh with out_shardings so nothing is ever whole on
        # device 0 (parallel.sharding.init_on_mesh).  Threefry is
        # partitionable, so the values do not depend on the mesh.
        def make_params():
            return init_params(cfg, jax.random.key(config.seed))

        self._moe = cfg.is_moe
        # dp-attention locality (see EngineConfig.dp_attention_local).
        # Resolved BEFORE the pallas auto-selection: the kernel composes
        # with dp_attention only through locality (local slot rebase
        # inside the shard_map body — ISSUE 9 leg 2).
        self._dp_local = config.dp_attention_local
        if self._dp_local is None:
            self._dp_local = (config.dp_attention
                              and self.mesh is not None
                              and not config.enable_prefix_cache)
        if self._dp_local and (self.mesh is None
                               or not config.dp_attention):
            raise ValueError("dp_attention_local needs a mesh with "
                             "dp_attention")
        if self._dp_local and config.enable_prefix_cache:
            raise ValueError("dp_attention_local needs the plain "
                             "allocator (enable_prefix_cache=False); the "
                             "tiered source has no shard concept yet")
        # Auto pallas: on for TPU, except under a dp_attention mesh
        # WITHOUT page locality (pages may live on any shard — an
        # EXPLICIT use_pallas_decode=True there is rejected loudly by
        # make_sharded_step rather than silently downgraded) or when the
        # per-shard cache feature width can't satisfy Mosaic's DMA tiling
        # (F % 128, block % 8 — small test models fall back to gather).
        # dp_attention slot-shards the cache, so every shard keeps the
        # FULL feature width; head-sharded tp splits it.
        pallas = config.use_pallas_decode
        if pallas is None:
            if self.mesh is not None and config.dp_attention:
                feat = cfg.num_kv_heads * cfg.head_dim
            else:
                tp = (self.mesh.shape["tp"] if self.mesh is not None
                      else 1)
                feat = cfg.kv_feature_dim // max(tp, 1)
            # Eligibility beyond geometry comes from the capability
            # table (non-local dp_attention, pp stage scan, lockstep
            # shard_map are all declared there) — querying it instead of
            # re-listing the combos keeps auto-pallas from drifting when
            # the table changes.
            pallas = (jax.default_backend() == "tpu"
                      and self._kernels_eligible(feat)
                      and plane_capability(
                          self.mesh,
                          PlaneSpec(use_pallas=True,
                                    dp_attention=(config.dp_attention
                                                  and self.mesh
                                                  is not None),
                                    dp_local=bool(self._dp_local)),
                          multihost=self._mh).ok)
        self._use_pallas = pallas
        self._n_local_shards = 1
        if self._dp_local:
            self._n_local_shards = (self.mesh.shape["dp"]
                                    * self.mesh.shape["tp"])
            if config.num_blocks % self._n_local_shards:
                raise ValueError(
                    f"dp_attention_local: num_blocks={config.num_blocks} "
                    f"must divide by dp*tp={self._n_local_shards}")
            if sched_cfg.max_seqs % self._n_local_shards:
                raise ValueError(
                    f"dp_attention_local: max_seqs={sched_cfg.max_seqs} "
                    f"must divide by dp*tp={self._n_local_shards}")
        self._pp = (self.mesh is not None
                    and self.mesh.shape.get("pp", 1) > 1)
        # Raw (pre-jit) forward for the fused greedy single step
        # (_greedy_step_fn) on meshless engines; sharded (non-pp,
        # single-process) engines build their fused step through
        # parallel.sharding.make_sharded_greedy_step instead (ISSUE 9
        # leg 3 — the sharded single-step cliff).
        self._fwd_raw: Optional[Callable] = None
        if self._mh and self._pp:
            raise ValueError("pipeline parallelism under a multi-process "
                             "mesh is not wired yet (multihost v1 covers "
                             "tp/dp/dp-attention)")
        self._sp_step = None
        self._sp_pallas = False  # sp prefill step built with the kernel
        self.sp_prefill_count = 0  # served prefills that ran the ring path
        if self._pp:
            # Pipeline serving: stage-rotated GPipe step over the pp axis.
            # v2: the stacked layout has its own whole-block extract/
            # inject (pipeline.make_pp_block_ops), so the tiered prefix
            # cache runs under pp like everywhere else.  v3 (ISSUE 12):
            # the stacked layout grows sibling scale buffers, so int8
            # serves pp like everywhere else too.
            from dynamo_tpu.parallel.pipeline import (
                init_pp_cache, make_pp_step, pp_cache_pspecs,
                pp_param_pspecs, stack_layer_params)

            if params is None:
                params = init_on_mesh(
                    lambda: stack_layer_params(make_params()),
                    pp_param_pspecs(cfg), self.mesh)
            else:
                params = shard_pytree(stack_layer_params(params),
                                      pp_param_pspecs(cfg), self.mesh)
            self._step = make_pp_step(cfg, self.block_size, self.mesh,
                                      config.pp_microbatches,
                                      kv_quant=self.cache_cfg.quantized)
            cache = init_on_mesh(
                lambda: init_pp_cache(self.cache_cfg),
                pp_cache_pspecs(self.cache_cfg.quantized), self.mesh)
        elif self.mesh is not None:
            from dynamo_tpu.parallel.sharding import resolve_moe_mode

            moe_mode = resolve_moe_mode(cfg, self.mesh, config.moe_mode)
            self._moe_mode = moe_mode
            pspecs = param_pspecs(cfg, moe_mode,
                                  dp_attention=config.dp_attention)
            params = (init_on_mesh(make_params, pspecs, self.mesh)
                      if params is None
                      else shard_pytree(params, pspecs, self.mesh))
            self._step = make_sharded_step(
                cfg, self.block_size, self.mesh,
                PlaneSpec(quant=self.cache_cfg.quantized,
                          dp_attention=config.dp_attention,
                          use_pallas=pallas, dp_local=self._dp_local),
                self._moe, moe_mode=moe_mode)
            cache = init_on_mesh(
                lambda: kvc.init_cache(self.cache_cfg),
                cache_pspecs(cfg.num_layers,
                             dp_attention=config.dp_attention,
                             dp_local=self._dp_local,
                             kv_quant=self.cache_cfg.quantized),
                self.mesh)
            if (self.mesh.shape.get("sp", 1) > 1
                    and plane_capability(
                        self.mesh,
                        PlaneSpec(role="sp_prefill", moe=cfg.is_moe,
                                  dp_attention=config.dp_attention),
                        multihost=self._mh).ok):
                # Eligibility comes from the capability table (moe ×
                # ring-SP and dp_attention × ring-SP are both declared
                # impossible there) instead of a hand-coded combo list.
                from dynamo_tpu.parallel.sharding import make_sp_prefill_step

                # Pallas flash ring rides the same auto-pallas decision
                # as decode, re-checked against the capability table
                # with the sp_prefill role (multihost shard_map custom
                # calls stay declared out); per-dispatch geometry
                # eligibility is the kernel's own shared predicate at
                # trace time (llama._sp_ring_attention).
                self._sp_pallas = bool(pallas) and plane_capability(
                    self.mesh,
                    PlaneSpec(role="sp_prefill", moe=cfg.is_moe,
                              quant=self.cache_cfg.quantized,
                              use_pallas=True,
                              dp_attention=config.dp_attention),
                    multihost=self._mh).ok
                self._sp_step = make_sp_prefill_step(
                    cfg, self.block_size, self.mesh,
                    kv_quant=self.cache_cfg.quantized,
                    use_pallas=self._sp_pallas)
        else:
            from dynamo_tpu.parallel.sharding import resolve_moe_mode

            # Meshless MoE mode: "auto" picks the grouped Pallas fast
            # path on TPU (eligible geometry) and the dense oracle
            # elsewhere — the same one-resolver discipline as meshes.
            moe_mode = resolve_moe_mode(cfg, None, config.moe_mode)
            self._moe_mode = moe_mode
            fwd = make_forward_step(cfg, self.block_size,
                                    use_pallas_decode=pallas,
                                    moe_mode=moe_mode,
                                    with_expert_load=self._moe,
                                    moe_aux=self._moe)
            # Before the weights and the KV pool are made: the store
            # loads this engine's programs while the rest of it is built.
            program_store.read_ahead(config.program_store,
                                     self._program_family())
            if self._ssm:
                inner = fwd

                # The step programs take their arguments by position: the
                # rows' state slots ride last.
                def step(params, cache, tokens, positions, seq_lens, bts,
                         sample_pos, state_slots):
                    return inner(params, cache, tokens, positions, seq_lens,
                                 bts, sample_pos, state_slots=state_slots)

                fwd = step
            fwd = self._window_tables_last(fwd)
            self._step = self._stored(
                jax.jit(fwd, donate_argnums=(1,)), "step")
            self._fwd_raw = fwd
            if params is None:
                params = jax.jit(make_params)()
            cache = kvc.init_cache(self.cache_cfg)
        # Modeled-bytes honesty under meshes (ISSUE 9 satellite) needs
        # TWO per-chip divisors, because residency and read traffic
        # shard differently:
        # - `kv_shard_count` (RESIDENCY — dynamo_kv_bytes_per_block):
        #   how many chips one stored KV byte splits across.  Head-
        #   sharded tp and dp_attention split the cache tp-ways
        #   (features vs slots), dp-local over the flat (dp, tp) grid,
        #   pp splits the LAYERS over stages; plain dp REPLICATES the
        #   cache per replica — no division.
        # - `kv_traffic_shards` (READ TRAFFIC — kv_read_bytes_modeled /
        #   effective_bytes_per_token): batch rows shard over dp (and
        #   over (dp, tp) under dp_attention), so each chip's attention
        #   sweeps only its rows' context — per-chip traffic divides by
        #   dp*tp on every non-pp mesh even where residency doesn't
        #   (plain dp: full cache resident, half the rows read).  A pp
        #   stage reads its layer slice for ALL rows: divide by pp.
        if self._pp:
            self.kv_shard_count = self.mesh.shape["pp"]
            self.kv_traffic_shards = self.mesh.shape["pp"]
        elif self.mesh is not None:
            self.kv_traffic_shards = (self.mesh.shape["dp"]
                                      * self.mesh.shape["tp"])
            self.kv_shard_count = (self.kv_traffic_shards if self._dp_local
                                   else max(self.mesh.shape["tp"], 1))
        else:
            self.kv_shard_count = self.kv_traffic_shards = 1
        # Per-chip KV bytes one decode step reads per context token.
        self._ctx_token_bytes_chip = (
            self.cache_cfg.bytes_per_context_token
            / self.kv_traffic_shards)
        # Cumulative per-expert assignment counts (MoE telemetry the
        # worker publishes; reference `base_handlers.py:40-62`) and the
        # capacity-honesty counter: every step's stats vector is [E+1]
        # (ops/moe.py), whose tail counts assignments a bounded
        # `moe_capacity` dropped — 0 forever at the exact default.
        self.expert_load = (np.zeros((cfg.num_experts,), np.int64)
                            if self._moe else None)
        self.moe_dropped_tokens = 0
        self._load_dev = None  # device-side [E+1] accumulator (lazy sync)
        # Beside it, where a program reports them: distinct experts that
        # got a row, summed over layers (device scalar), and how many
        # expert layers ran (host int) since the last sync.
        self._touched_dev = None
        self._moe_layers_pending = 0
        # The same two for causal decode alone (windows, single steps): what
        # a decode step's share of its HBM roofline is reckoned from.
        self._touched_decode_dev = None
        self._moe_decode_layers_pending = 0
        # The same for the calls dispatched while a device capture runs,
        # for a model that holds a share of its experts: int32 [2, 2] on the
        # device, rows (decode, prefill), columns (assignments of the
        # experts held here, held experts touched), the expert layers each
        # row covers, and the one small program that adds to it (compiled
        # here, not inside a capture).
        self._moe_capture_dev = None
        self._moe_capture_layers = [0, 0]
        self._capture_tally = None
        if self._moe and cfg.experts_held is not None:
            first, count = cfg.experts_held

            def tally(acc, load, touched, row):
                return acc.at[row].add(jnp.stack(
                    [jnp.sum(load[first:first + count]), touched]
                ).astype(jnp.int32))

            self._capture_tally = jax.jit(tally)
            self._capture_tally(
                jnp.zeros((2, 2), jnp.int32),
                jnp.zeros((cfg.num_experts + 1,), jnp.int32),
                jnp.zeros((), jnp.int32), 0)
        # Rows of the packed buffers those expert layers handed the grouped
        # kernel (host int, from the programs' static shapes: `packed_rows`).
        self._moe_packed_pending = 0
        # Folded into the host tallies (a window's read does that) and not
        # yet copied to `metrics.expert_load`.
        self._moe_unpublished = False
        self._block_fns: Dict[tuple, Callable] = {}
        # The block path reads one call behind: the block program call
        # that is dispatched and not yet read, and tokens read outside
        # `step()` (a drain) that the next `step()` hands out.
        self._block_unread: Optional[dict] = None
        self._block_held: List[TokenDelta] = []
        # A list, while a comparison records: the block path appends one
        # entry a call (its program's trail), the prefill one entry a call
        # with the experts it chose.  With `block_record_logits` the block
        # path runs the variant of its program whose trail holds every
        # forward's logits (few rows only: 0.8 GB at 64); without, the
        # programs are the served ones.
        self.block_record: Optional[list] = None
        self.block_record_logits = True
        self._embed_step = None  # lazily compiled (embeddings route)
        self._mm_step = None     # lazily compiled (multimodal prefill)
        # Fused greedy single step (forward + on-device argmax in ONE
        # compiled program, donated cache) — the non-window decode path's
        # steady shape.  Unsharded engines only (self._fwd_raw); lazily
        # jitted on first all-greedy single-step decode.
        self._greedy_fused: Optional[Callable] = None
        # Packed ragged prefill plane (EngineConfig.packed_prefill).
        # The kernel's T % PACK_ALIGN contract binds in interpret mode
        # too, so token buckets DERIVED from prefill_buckets must be
        # aligned just like explicit packed_prefill_buckets (which
        # SchedulerConfig validates itself): auto treats a misaligned
        # ladder as ineligible, explicit-on rejects it at construction.
        from dynamo_tpu.ops.pallas import PACK_ALIGN as _pack_align

        packed = config.packed_prefill
        _bad_buckets = [b for b in sched_cfg.packed_buckets()
                        if b % _pack_align]
        if packed is None:
            packed = (jax.default_backend() == "tpu"
                      and self.mesh is None and not self._mh
                      and not _bad_buckets
                      and self._kernels_eligible(cfg.kv_feature_dim))
        elif packed:
            if _bad_buckets:
                raise ValueError(
                    f"packed_prefill=True but the token buckets derived "
                    f"from prefill_buckets are not {_pack_align}-aligned "
                    f"({_bad_buckets}) — the packed kernel's PACK_ALIGN "
                    "contract; align prefill_buckets or set "
                    "packed_prefill_buckets explicitly")
            if self.mesh is not None or self._mh:
                raise ValueError(
                    "packed_prefill is meshless v1 (the packed step has "
                    "no sharded variant yet); drop packed_prefill or the "
                    "mesh — sharded engines keep the padded plane")
            if jax.default_backend() == "tpu":
                # Same eligibility the auto rule applies: fail at
                # construction with a pointed config error instead of a
                # Mosaic lowering error on the first prefill (off-TPU
                # the kernel runs in interpret mode, any geometry).
                if not self._kernels_eligible(cfg.kv_feature_dim):
                    raise ValueError(
                        "packed_prefill=True but the geometry is not "
                        "Mosaic-eligible (needs num_kv_heads*head_dim % "
                        "128 == 0 and block_size % 8 == 0; got "
                        f"F={cfg.kv_feature_dim}, "
                        f"block_size={self.block_size}) — drop the flag "
                        "to serve this model through the padded plane")
        self._use_packed_prefill = bool(packed)
        self._packed_step: Optional[Callable] = None  # lazily jitted
        # What `note_window_interval` is fed: the token bucket of the
        # chunk that rode since the last window dispatch (0: none; it is
        # attributed to the window whose sync interval absorbs it), the
        # previous window-sync timestamp (None across pipeline drains:
        # fill and drain intervals are no steady-state samples) and the
        # clock both are read on.
        self._chunk_key = 0
        self._clock = time.monotonic
        self._last_window_sync_ts: Optional[float] = None
        # Speculative decoding: pluggable drafter + lazily-jitted batched
        # verify (sampling.speculative_verify).  Mesh-level eligibility
        # comes from the capability table (checked loudly above);
        # per-step conditions (logprobs, seeded rows, prefill backlog)
        # stay in _spec_eligible.
        self._spec_capable = plane_capability(
            self.mesh,
            PlaneSpec(spec=True, quant=self.cache_cfg.quantized,
                      dp_attention=config.dp_attention,
                      dp_local=self._dp_local),
            multihost=self._mh).ok
        self._spec_verify: Optional[Callable] = None
        if config.drafter is not None:
            self._drafter = config.drafter
        else:
            from dynamo_tpu.engine.drafter import NgramDrafter

            self._drafter = NgramDrafter(config.speculative_ngram)
        # Constant per-bucket device arrays the decode path re-used to
        # upload EVERY step (sample_positions is always zeros for T=1).
        self._zeros_dev: Dict[int, object] = {}
        self._window_fns: Dict[bool, Callable] = {}
        self._window_state: Optional[Dict] = None  # device-resident rows
        self._inflight: List = []  # dispatched-unsynced decode windows
        self._async_copy_warned = False  # copy_to_host_async probe, once
        # FOUR fetch threads: device execution serializes windows, but the
        # device→host copies are independent per window, so concurrent
        # fetches overlap their latencies; per-window ordering still
        # holds because _sync_one_window waits on each entry's own future
        # in dispatch order.  (Sized for a copy latency the attached chip
        # does not have: ROADMAP queue item.)
        from concurrent.futures import ThreadPoolExecutor
        self._fetch_pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="kv-window-fetch")
        # Async prefill-completion sampling (mixed window mode): request
        # ids whose first token is still in flight + their fetch futures.
        self._pending_first: set = set()
        self._pending_batches: List[tuple] = []
        self.params = params
        self.cache = cache

        # Block source: tiered, prefix-caching KVBM by default (ADVICE r1:
        # it must actually be wired, not just exist); plain free list when
        # prefix caching is off.  The managed source owns residency truth,
        # so REMOVED events come from its eviction hook rather than from
        # request finish.
        # A model with state-space layers takes the no-reuse source (a
        # prefix match always misses): a cached page says nothing of the
        # recurrent state at its end, and no snapshot of it is kept.
        # So does a model with window layers: a cached prefix's window-group
        # pages went back to their pool as the sequence moved on.
        self._managed_cache = (config.enable_prefix_cache and not self._ssm
                               and not self._window)
        if self._managed_cache:
            from dynamo_tpu.llm.block_manager.engine_source import (
                ManagedBlockSource,
            )
            from dynamo_tpu.llm.block_manager.manager import TieredConfig

            if self._pp:
                # Stacked layout: its own block ops (same canonical
                # [2, L, bs, F] block — offload/transfer stay
                # layout-agnostic).
                from dynamo_tpu.parallel.pipeline import make_pp_block_ops

                self._extract_jit, self._inject_jit = make_pp_block_ops(
                    self.block_size, self.mesh,
                    kv_quant=self.cache_cfg.quantized)
            elif self._mh:
                from dynamo_tpu.parallel.sharding import (
                    cache_pspecs as _cps)

                # (ISSUE 12 leg 4 audit: the spec tree must carry the
                # scale leaves under int8 or the multihost block ops
                # would tree-mismatch on first extract.)
                self._extract_jit, self._inject_jit = kvc.make_block_ops(
                    self.block_size, mesh=self.mesh,
                    cache_specs=_cps(cfg.num_layers, config.dp_attention,
                                     self._dp_local,
                                     self.cache_cfg.quantized))
            else:
                self._extract_jit, self._inject_jit = kvc.make_block_ops(
                    self.block_size, constrain_mesh=self.mesh)
            self.allocator = ManagedBlockSource(
                TieredConfig(
                    device_blocks=config.num_blocks,
                    host_blocks=config.host_blocks,
                    disk_blocks=config.disk_blocks,
                    block_size=self.block_size,
                ),
                extract_fn=self._extract_block,
                inject_fn=self._inject_block,
                on_removed=self._on_block_evicted,
                remote_fetch_fn=config.remote_fetch_fn,
            )
        else:
            self.allocator = BlockAllocator(
                config.num_blocks, num_shards=self._n_local_shards)
        if self._dp_local:
            # Fixed decode row grid: row == slot, so a request's rows ride
            # one device for its whole lifetime and shard_of_slot is
            # stable (compaction would migrate rows across shards).
            import dataclasses as _dc

            self._dp_rows = sched_cfg.bucket_for_decode(sched_cfg.max_seqs)
            if self._dp_rows % self._n_local_shards:
                raise ValueError(
                    f"dp_attention_local: decode bucket {self._dp_rows} "
                    f"must divide by dp*tp={self._n_local_shards}")
            rows_per_shard = self._dp_rows // self._n_local_shards
            sched_cfg = _dc.replace(
                sched_cfg,
                shard_of_slot=lambda s: s // rows_per_shard)
        self.window_allocator = (
            BlockAllocator(self.cache_cfg.window_blocks) if self._window
            else None)
        self.scheduler = Scheduler(sched_cfg, self.allocator,
                                   self.window_allocator, cfg.max_window)
        # QoS preemption (ISSUE 15 leg 3): the scheduler picks victims,
        # the engine executes the preempt so seal bookkeeping resets and
        # the victim's sealed KV demotes to the host tier (resume is a
        # tier onboard, not a re-prefill).
        self.scheduler.qos_preempt_sink = self._qos_preempt
        self.qos_demoted_blocks = 0

        # Padding writes target this position; it indexes past every
        # runtime table width, so slots_for_positions resolves it to the
        # null block (tables are bucket-sliced — see bucket_for_pages).
        self._pad_position = sched_cfg.max_pages_per_seq * self.block_size
        # Sharded batch axes demand divisibility: rows pad up to a
        # multiple of dp (dp*tp under dp_attention, whose batch shards
        # over both axes; the microbatch count under pp).
        if self._pp:
            self._row_mult = config.pp_microbatches
        elif self.mesh is not None:
            self._row_mult = self.mesh.shape["dp"] * (
                self.mesh.shape["tp"] if config.dp_attention else 1)
            if getattr(self, "_moe_mode", "dense") == "dispatch":
                # The all-to-all shard_map shards tokens over dp x ep;
                # batch rows must divide by both.
                self._row_mult *= self.mesh.shape["ep"]
        else:
            self._row_mult = 1
        self._requests: Dict[str, Request] = {}
        self._hash_seqs: Dict[str, TokenBlockSequence] = {}
        self._published_blocks: Dict[str, int] = {}  # req -> #blocks published
        self._kv_event_sink = kv_event_sink
        self._event_id = 0
        self._rng = jax.random.key(config.seed + 1)
        self.step_count = 0
        # Serving-loop overhead counters (runtime/metrics.py): host syncs
        # and compiled-shape cache misses, with dispatch denominators —
        # the observability the r5 single-step cliff lacked.
        # The scheduler's transitions and the core's run on one
        # request-state clock: the scheduler's counters are the engine's.
        self.counters = self.scheduler.counters
        if self._ssm:
            self.counters.ssm_slots_capacity = sched_cfg.max_seqs
            self.counters.ssm_state_bytes_per_slot = \
                self.cache_cfg.state_bytes_per_slot
        if cfg.has_pattern:
            self.counters.model_layers = {
                "ssm": len(cfg.state_layers),
                "attention": len(cfg.attention_layers),
                "moe": cfg.num_moe_layers}
        if self._window:
            kinds = {"window": len(cfg.window_layers),
                     "full": len(cfg.attention_layers)
                     - len(cfg.window_layers)}
            self.counters.model_layers = dict(kinds)
            self.counters.attn_kind_layers = kinds
            self.counters.attn_window = cfg.max_window
            self.counters.window_pool["capacity"] = \
                self.cache_cfg.window_blocks - 1
        # Flight recorder (runtime/flight_recorder.py): the postmortem
        # ring.  step() stamps its heartbeat unconditionally (the stall
        # watchdog reads it); dispatch-shape / admission / recompile
        # breadcrumbs record only while the process enabled the ring
        # (worker --flight-recorder), and every record site passes
        # pre-computed scalars only (lint rule DL006).
        self.flight = flight_recorder.get_recorder()
        self.counters.on_recompile = self._flight_recompile
        # Device-truth plane (runtime/device_profiler.py): on first-seen
        # shapes the dispatch sites hand the about-to-compile callable +
        # args to _harvest_program, which records XLA's cost analysis
        # (flops / bytes accessed) in the program registry.  Disabled by
        # default; worker --device-profiler enables it.  Zero steady-path
        # cost: the harvest rides the compile event only.
        self.profiler = device_profiler.get_profiler()
        # A device capture turns this clock's phases into events of the
        # trace (EngineStepCounters.enter, sink 2).
        self.profiler.watch_phases(self.counters)
        # What `_end_step` classifies an iteration's chance to prefill by:
        # the dispatch tallies as the iteration found them.
        self._step_dispatches0 = self._step_prefills0 = 0
        # The rule of mixed prefill (`_chunk_rides`): the seconds of
        # credit chunks have with decode, and whether a chunk rode behind
        # the last window (all its clock-free form goes by).  A window
        # engine on one host lifts the scheduler's static cap for good: a
        # planned chunk is whatever waits, up to max_prefill_chunk.
        self._chunk_credit_s = 0.0
        self._chunk_rode = False
        if (config.decode_window > 1 and config.speculative_tokens == 0
                and not self._mh and not self._diffusion):
            self.scheduler.mixed_budget_override = sched_cfg.max_prefill_chunk
        # Prefill seal-progress sink (disagg eager KV streaming): called
        # on the engine thread with (request_id, sealed_block_count) as
        # blocks seal.  Pure host bookkeeping piggybacking on the hashing
        # _publish_completed_blocks already does — no device work, no
        # host syncs, no spans.
        self.seal_sink: Optional[Callable[[str, int], None]] = None
        self.metrics = ForwardPassMetrics(
            worker_stats=WorkerStats(
                request_total_slots=config.scheduler.max_seqs),
            kv_stats=KvStats(kv_total_blocks=config.num_blocks - 1),
            spec_decode_stats=(SpecDecodeStats()
                               if config.speculative_tokens > 0 else None),
        )
        # The one line that says where this engine really runs and what
        # `auto` resolved to (chip_smoke.py fails unless it reads tpu
        # with both kernel planes on).
        devices = jax.devices()
        logger.info(
            "engine built: platform=%s device_kind=%r devices=%d "
            "pallas_decode=%s packed_prefill=%s moe_mode=%s",
            devices[0].platform, devices[0].device_kind, len(devices),
            bool(self._use_pallas), self._use_packed_prefill,
            getattr(self, "_moe_mode", "dense"))

    # -- request lifecycle ------------------------------------------------

    @engine_thread_only
    def add_request(
        self,
        request_id: str,
        prompt_tokens: List[int],
        sampling: SamplingParams,
        prompt_embeds=None,
        priority: int = 1,
    ) -> None:
        if request_id in self._requests:
            raise ValueError(f"duplicate request id {request_id}")
        if not prompt_tokens:
            raise ValueError("empty prompt")
        if prompt_embeds is not None:
            # Declared-impossible combos (pp / multihost) raise the
            # capability table's pointed error — one source of truth.
            check_plane(self.mesh, PlaneSpec(role="mm"),
                        multihost=self._mh)
            prompt_embeds = np.asarray(prompt_embeds)
            if (prompt_embeds.ndim != 2
                    or prompt_embeds.shape[0] > len(prompt_tokens)
                    or prompt_embeds.shape[1]
                    != self.config.model.hidden_size):
                raise ValueError(
                    f"prompt_embeds shape {prompt_embeds.shape} must be "
                    f"[n <= {len(prompt_tokens)}, "
                    f"{self.config.model.hidden_size}]")
        if self._lockstep is not None:
            from dynamo_tpu.parallel.multihost import encode_sampling

            self._lockstep.broadcast({
                "op": "add", "rid": request_id,
                "prompt": list(prompt_tokens),
                "sampling": encode_sampling(sampling),
                "priority": int(priority)})
        req = Request(request_id=request_id,
                      prompt_tokens=list(prompt_tokens), sampling=sampling,
                      prompt_embeds=prompt_embeds,
                      priority=int(priority))
        if prompt_embeds is not None:
            # Placeholder tokens must neither match nor seed the prefix
            # cache (different images share placeholder ids).
            req.block_hashes = ()
        self._requests[request_id] = req
        self.scheduler.add_request(req)

    @engine_thread_only
    def cancel(self, request_id: str) -> None:
        req = self._requests.get(request_id)
        if req and req.state is not RequestState.FINISHED:
            self.drain_block_call()
            if req.state is RequestState.FINISHED:
                return              # ended inside the block just read
            if self._lockstep is not None:
                self._lockstep.broadcast({"op": "cancel",
                                          "rid": request_id})
            self._finish(req, FinishReason.CANCELLED)

    def has_request(self, request_id: str) -> bool:
        return request_id in self._requests

    @property
    def has_work(self) -> bool:
        """True while any request needs a step() — including finished ones
        whose terminal delta hasn't been collected yet (admission-rejected
        and cancelled requests only surface through _collect_dead) — or
        the block path holds a call it has not read or tokens it has not
        handed out."""
        return bool(self._requests or self._block_unread is not None
                    or self._block_held)

    @property
    def has_pending_prefill(self) -> bool:
        """True while any request still owes prefill work (queued or
        mid-chunk) — the public form of the "drain prefill before timing
        decode" loop the planner's profilers need, so external drivers
        never reach into `_requests`."""
        return any(r.state in (RequestState.WAITING, RequestState.PREFILL)
                   for r in self._requests.values())

    # -- stepping ---------------------------------------------------------

    @engine_thread_only
    @hot_path
    def step(self, deliver: Optional[Callable[[List[TokenDelta]], None]]
             = None) -> List[TokenDelta]:
        """Run one engine iteration; returns token deltas (may be empty).
        With `deliver` (the serving loop's hand-over) the deltas an
        iteration holds while it reads a drain go to it window by window
        (`_drain_inflight`), and only the rest is returned.

        Steady-state decode (no prefill, no admissions, stable request
        set) runs through the pipelined window path: dispatch one fused
        K-token window, sync the window from `window_pipeline_depth`
        dispatches ago.  Any scheduling change drains the pipeline first
        so host bookkeeping never diverges from device state.

        MIXED prefill+decode: windows keep running while prompts wait,
        and a prefill chunk (whatever waits, up to `max_prefill_chunk`)
        rides behind a window on the device queue when `_chunk_rides`
        says so.  Every dispatched window earns chunks
        (1 - DECODE_SHARE) / DECODE_SHARE of its measured seconds; a
        chunk rides once that credit covers what its token bucket was
        measured to cost, at most one between two windows.  So decode
        keeps DECODE_SHARE of the device's seconds whatever the rows
        decoding.  A chunk that completes a prompt samples its
        first token asynchronously, and the row joins the decode cohort
        at a merge, which costs one pipeline drain.  Where the row would
        force that merge as soon as its token settled (`_window_work`'s
        rule: a small cohort, or no prompt left to prefill) the pipeline
        HOLDS at the completing chunk: no further window of the old
        cohort goes out, the next iteration reads the windows in flight
        (all dispatched before the chunk; their tokens go to the serving
        loop window by window, `_drain_inflight`), waits for the token
        (one counted sync, the chunk's own time at most) and dispatches
        the merged cohort: `... window, chunk, merged window` on the
        device.  (Hold and hand-over need each other: a first token
        handed over as it is read, with the merge left to its settle,
        puts the wait for the cohort between a client's first and second
        token.)
        Below the rule's threshold (a large cohort with prompts still
        queued) windows keep going and completed rows collect in a ready
        pool, merged in batches so the pipeline isn't drained per
        completion."""
        self.flight.beat()  # stall-watchdog heartbeat: one float store
        if self._lockstep is not None:
            self._lockstep.broadcast({"op": "step"})
        deltas: List[TokenDelta] = []
        c = self.counters
        enter = c.enter
        self._step_prefills0 = c.prefill_dispatches
        self._step_dispatches0 = c.decode_dispatches
        if self._diffusion:
            return self._step_blocks(deltas)
        self._settle_first_tokens(deltas, block=False)
        enter(PHASE_PLAN)
        plan = self.scheduler.plan()
        skipped = False

        work = self._window_work(plan)
        if work is None and (self._inflight or self._pending_batches):
            self._drain_inflight(deltas, deliver)
            # A first token still in flight was sampled by a chunk that
            # ran right behind the last window just read: waiting for it
            # here lets its row into the cohort built below instead of
            # costing that cohort a drain of its own.
            self._settle_first_tokens(deltas, block=True)
            enter(PHASE_PLAN)
            plan = self.scheduler.plan()  # finished reqs changed the plan
            work = self._window_work(plan)

        if work is not None:
            d = self._dispatch_window(work)
            if d is None:
                # Capacity refused under lookahead: drain and fall through
                # to the single-step path THIS iteration (it preempts
                # properly with non-shadowed state).  Merely returning here
                # would livelock — the next plan() is window-eligible
                # again and refuses again, forever (r2 shipped that bug:
                # tests/test_engine.py:306 stalled at 17 tokens).
                self._drain_inflight(deltas, deliver)
                enter(PHASE_PLAN)
                plan = self.scheduler.plan()
                work = None
            else:
                deltas.extend(d)
                if self._chunk_rides(plan.prefill):
                    # Concurrent prefill behind the window; first tokens
                    # fetch asynchronously (a blocking sample here would
                    # serialize every window behind a device sync).  A
                    # chunk passed over just replans next iteration
                    # (requests stay PREFILL).
                    deltas.extend(self._run_prefill_batch(
                        plan.prefill, async_first=not self._mh))
                else:
                    skipped = plan.prefill is not None
        if work is None and not plan.empty:
            # Single-step path: settle pending first tokens NOW — decode
            # work below reads output_tokens, and an unsettled request
            # would double-sample its first token.  The settle can FINISH
            # requests (stop token / max_tokens=1), so the plan must be
            # recomputed — the stale one would hand a finished request to
            # _run_decode (page re-allocation for a dead request, double
            # finished delta).
            if self._pending_batches:
                self._settle_first_tokens(deltas, block=True)
                enter(PHASE_PLAN)
                plan = self.scheduler.plan()
            if plan.prefill:
                # A window cohort on its way out (its last tokens, a row
                # that wants logprobs) keeps its share step by step.
                if (plan.decode is None or self._mh
                        or self.counters.window_s is None
                        or self._chunk_rides(
                            plan.prefill, 1.0 / self.config.decode_window)):
                    deltas.extend(self._run_prefill_batch(plan.prefill))
                else:
                    skipped = True
            if plan.decode:
                d = (self._run_decode_spec(plan.decode)
                     if self._spec_eligible(plan) else None)
                if d is None:
                    d = self._run_decode(plan.decode)
                deltas.extend(d)

        return self._end_step(deltas, skipped)

    @hot_path
    def _end_step(self, deltas: List[TokenDelta],
                  skipped: bool = False) -> List[TokenDelta]:
        """What every iteration ends with, whichever path it took.

        First, what became of its one chance to dispatch a prefill chunk
        (`prefill_chances`): `dispatched` if a prefill program was; else,
        if a request is left in `budget_wait` or `prefill`, `duty_skipped`
        (a chunk was planned and `_chunk_rides` passed it over),
        `no_window` (nothing at all was dispatched: a drain) or
        `no_budget` (decode work went out, the plan held no chunk).  An
        iteration with no such request and no chunk had no chance."""
        c = self.counters
        n = c.req_state_n
        if c.prefill_dispatches != self._step_prefills0:
            c.prefill_chances[CHANCE_DISPATCHED] += 1
        elif n[RS_BUDGET_WAIT] or n[RS_PREFILL]:
            c.prefill_chances[
                CHANCE_DUTY_SKIPPED if skipped
                else CHANCE_NO_WINDOW
                if self._step_dispatches0 == c.decode_dispatches
                else CHANCE_NO_BUDGET] += 1
        c.enter(PHASE_DELIVER)
        self._collect_dead(deltas)
        self.step_count += 1
        if self.flight.enabled and self.step_count % 64 == 0:
            # Periodic cumulative-counter breadcrumb: consecutive
            # "counters" events give the postmortem reader per-interval
            # DELTAS of syncs/recompiles/dispatches; cadence 64 keeps it
            # inside the steady-window ring-write budget.
            self._flight_counters()
        self._refresh_metrics()
        return deltas

    def _step_blocks(self, deltas: List[TokenDelta]) -> List[TokenDelta]:
        """One iteration of a block-diffusion engine, which keeps one block
        program call in flight and reads one call behind: plan, the
        scheduler's prefill chunk (whole blocks of prompts, no token
        sampled from it), then the next block call over every decoding
        sequence, built from host bookkeeping alone, and only then the
        blocking read of the call before it, whose tokens are emitted
        while the device runs the new one.  A sequence's next block is B
        mask tokens one block on (what the unread block decided reaches it
        through the cache), so the read is needed only to stream and to
        learn of a stop token; a sequence that finished prefill here
        decodes from the next iteration on.

        The unread call is read at once (drained) when a sequence is out
        of pages (preempting it needs its tokens), when no sequence is
        left to dispatch behind it (a last block waits for no further
        iteration), and before anything outside `step()` touches requests
        or the cache (`drain_block_call`)."""
        enter = self.counters.enter
        if self._block_held:
            deltas.extend(self._block_held)
            self._block_held = []
        enter(PHASE_PLAN)
        # One chunk a block call: a chunk of up to max_prefill_chunk
        # tokens costs about one forward (it streams the same expert
        # weights), a block call several.
        self.scheduler.mixed_budget_override = \
            self.scheduler.config.max_prefill_chunk
        plan = self.scheduler.plan()
        rows = None
        if plan.decode:
            rows = self._block_rows(plan.decode.requests)
            if rows is None:
                deltas.extend(self._drain_block())
                rows = self._block_rows(plan.decode.requests)
        if plan.prefill:
            deltas.extend(self._run_prefill_batch(plan.prefill))
        unread = self._block_unread
        self._block_unread = self._run_block_decode(rows) if rows else None
        if unread is not None:
            deltas.extend(self._read_block(unread))
        if self._block_unread is not None and self._block_call_is_last():
            deltas.extend(self._drain_block())
        return self._end_step(deltas)

    def _block_fn(self, greedy_only: bool, record: bool = False):
        """The block program (llama.make_block_step), jitted with the
        cache donated and served from the program store."""
        fn = self._block_fns.get((greedy_only, record))
        if fn is None:
            from dynamo_tpu.models.llama import make_block_step

            fn = self._stored(
                jax.jit(
                    make_block_step(
                        self.config.model, self.block_size,
                        use_pallas_decode=self._use_pallas,
                        greedy_only=greedy_only,
                        moe_mode=self._moe_mode, record=record),
                    donate_argnums=(1,)),
                "block", greedy_only=greedy_only, record=record)
            self._block_fns[(greedy_only, record)] = fn
        return fn

    def _next_block(self, req: Request) -> Optional[Tuple[int, int]]:
        """(start, known positions) of the block `req` decodes next, or
        None if its `max_tokens` ends inside the block it has in the
        unread call.  A sequence of `total_len` known tokens has its first
        `c = B * (total_len // B)` positions committed to the cache: its
        block is [c, c + B), the first `total_len - c` positions known (a
        prompt's tail).  One whose block is unread continues a block on,
        with nothing known."""
        B = self.config.model.diffusion_block_length
        unread = self._block_unread
        row = unread["rows"].get(req.request_id) if unread else None
        if row is None or row[0] is not req or row[3] != req.preempts:
            c = req.total_len // B * B
            return c, req.total_len - c
        _, start, known, _ = row
        if (req.prior_output + len(req.output_tokens) + B - known
                >= req.sampling.max_tokens):
            return None
        return start + B, 0

    def _block_rows(self, requests: List[Request]):
        """The rows of the next block call, [(request, start, known)] with
        pages reserved to each block's end, or None if a sequence is out
        of pages while a call is unread (read it first).  With nothing
        unread such a sequence is preempted or finished."""
        B = self.config.model.diffusion_block_length
        rows = []
        for req in requests:
            if req.state is not RequestState.DECODE:
                continue       # ended at the read a refusal forced
            nxt = self._next_block(req)
            if nxt is None:
                continue
            if not self.scheduler.ensure_capacity(req, nxt[0] + B):
                if self._block_unread is not None:
                    return None
                self._preempt_or_finish(req)
                continue
            rows.append((req,) + nxt)
        return rows

    def _block_call_is_last(self) -> bool:
        """Nothing will be dispatched behind the unread call: no request
        waits or prefills, and every decoding one ends inside it."""
        return not self.scheduler.waiting and not any(
            r.state is RequestState.PREFILL
            or self._next_block(r) is not None
            for r in self.scheduler.running)

    @hot_path
    def _run_block_decode(self, rows) -> dict:
        """Dispatch one block program call (denoising forwards and the
        commit) over `rows` of `_block_rows` and return it unread: nothing
        here waits for the device.  What `_read_block` will need is kept
        with the call, the expert-layer reports of the prefill chunks
        dispatched before it among it (taken now: the read of this call
        must wait on nothing dispatched after it).  The sampler's offsets
        count the tokens dispatched, those of an unread block included, so
        a seeded stream depends on the seed and the token index alone."""
        self.counters.enter(PHASE_DISPATCH_BLOCK)
        cfg = self.config.model
        B = cfg.diffusion_block_length
        bs = self.block_size
        sched = self.scheduler.config
        live = [r for r, _c, _k in rows]
        bucket = self._pad_rows(sched.bucket_for_decode(len(live)))
        pages = sched.bucket_for_pages(
            max((c + B + bs - 1) // bs for _r, c, _k in rows))
        tokens = np.zeros((bucket, B), np.int32)
        positions = np.full((bucket, B), self._pad_position, np.int32)
        seq_lens = np.zeros((bucket,), np.int32)
        bts = np.zeros((bucket, pages), np.int32)
        temp = np.zeros((bucket,), np.float32)
        top_k = np.zeros((bucket,), np.int32)
        top_p = np.ones((bucket,), np.float32)
        offsets = np.zeros((bucket,), np.int32)
        self._mark_decode(live)
        for i, (req, c, known) in enumerate(rows):
            tokens[i] = cfg.mask_token_id
            if known:
                n_prompt = len(req.prompt_tokens)
                tokens[i, :known] = (
                    req.output_tokens[c - n_prompt:] if c >= n_prompt
                    else req.prompt_tokens[c:] + req.output_tokens)
            positions[i] = np.arange(c, c + B)
            seq_lens[i] = c + B
            n = min(len(req.pages), pages)
            bts[i, :n] = req.pages[:n]
            temp[i] = req.sampling.temperature
            top_k[i] = req.sampling.top_k
            top_p[i] = req.sampling.top_p
            dispatched = c + known - len(req.prompt_tokens)
            offsets[i] = (req.sampling.seed_offset + req.prior_output
                          + dispatched) // B
        greedy = all(r.sampling.temperature <= 0 for r in live)
        key_data = (np.zeros((bucket, 2), np.uint32) if greedy
                    else self._block_keys(live, bucket))
        record = self.block_record is not None
        with_logits = record and self.block_record_logits
        self.counters.window_dispatches += 1
        if self._block_unread is not None:
            self.counters.block_calls_overlapped += 1
        first = self.counters.note_dispatch("block", greedy, with_logits,
                                            bucket, pages)
        fl = self.flight
        if fl.enabled:
            fl.record("block", bucket=bucket, pages=pages)
        fn = self._block_fn(greedy, with_logits)
        args = (self.params, self.cache, self._dev(tokens),
                self._dev(positions), self._dev(seq_lens), self._dev(bts),
                self._dev(temp), self._dev(top_k), self._dev(top_p),
                self._dev(key_data), self._dev(offsets))
        self._harvest_program(first, "block", (greedy, with_logits, bucket,
                                               pages), fn, args)
        out = fn(*args)
        self.cache = out[0]
        call = {"rows": {r.request_id: (r, c, k, r.preempts)
                         for r, c, k in rows},
                "out": (out[1], out[2], out[3], out[4] if record else None),
                "tokens": bucket * B,
                "chunks": (self._load_dev, self._touched_dev,
                           self._moe_layers_pending,
                           self._moe_packed_pending)}
        self._load_dev = self._touched_dev = None
        self._moe_layers_pending = self._moe_packed_pending = 0
        return call

    @hot_path
    def _read_block(self, call: dict) -> List[TokenDelta]:
        """The blocking read of a dispatched block call and what follows
        from it: the counters' tallies, a recording's entry, and its
        tokens emitted in order.  A row whose sequence ended after the
        dispatch (a stop token inside the block before, a preemption) is
        dropped whole: no token, no page published, no part in a
        recording.  What a block decides beyond `max_tokens` or a stop
        token is dropped with the sequence."""
        enter = self.counters.enter
        cfg = self.config.model
        B = cfg.diffusion_block_length
        # THE one counted sync of a block call: its tokens, its forward
        # count and what the expert layers reported up to its dispatch.
        self.counters.host_syncs += 1
        self.counters.window_syncs += 1
        enter(PHASE_WAIT_DEVICE)
        load, touched, chunk_layers, chunk_packed = call["chunks"]
        # dynamo-lint: disable=DL001 counted sync (host_syncs above)
        (toks, stats, moe, rec), load, touched = jax.device_get(
            (call["out"], load, touched))
        enter(PHASE_EMIT)
        rows = list(call["rows"].values())
        keep = [i for i, (req, _c, _k, preempts) in enumerate(rows)
                if self._requests.get(req.request_id) is req
                and req.state is RequestState.DECODE
                and req.preempts == preempts]
        denoise, unmasked, scored = (int(n) for n in stats)
        self.counters.note_block_step(
            len(rows), denoise, unmasked,
            int(moe["touched"]) if self._moe else 0,
            dropped=len(rows) - len(keep), scored=scored)
        # A forward that was not scored (the served programs' commit)
        # stopped at its last K/V write: one layer's attention sweep and
        # one expert layer less than a whole one.
        L = cfg.num_layers
        layer_forwards = L * (denoise + 1) - (denoise + 1 - scored)
        if self._moe:
            if load is not None:      # prefill chunks before the dispatch
                self._fold_moe_stats(load, touched, chunk_layers,
                                     packed=chunk_packed)
            self._fold_moe_stats(
                moe["load"], moe["touched"], layer_forwards,
                packed=layer_forwards * self._moe_packed_rows(call["tokens"]))
        self.counters.note_kv_read(
            sum(c + B for _r, c, _k, _p in rows) * layer_forwards
            * self._ctx_token_bytes_chip / L, 0)
        if rec is not None and self.block_record is not None and keep:
            n_fwd = denoise + 1
            trail = {k: v[:n_fwd] for k, v in rec.items()}
            if len(keep) < len(rows):
                # [F, L, R * B, k] by row like the others, and back.
                routing = trail.pop("routing", None)
                trail = {k: v[:, keep] for k, v in trail.items()}
                if routing is not None:
                    F, L, _n, k = routing.shape
                    trail["routing"] = routing.reshape(
                        F, L, -1, B, k)[:, :, keep].reshape(F, L, -1, k)
            self.block_record.append({
                "rids": [rows[i][0].request_id for i in keep],
                "starts": [rows[i][1] for i in keep],
                "known": [rows[i][2] for i in keep],
                "forwards": n_fwd, "tokens": toks[keep], **trail})
        deltas: List[TokenDelta] = []
        for i in keep:
            req, _c, known, _p = rows[i]
            for tok in toks[i, known:]:
                if (req.request_id not in self._requests
                        or req.state is not RequestState.DECODE):
                    break  # ended inside the block: the tail is dropped
                self._publish_completed_blocks(req)
                deltas.append(self._append_token(req, int(tok)))
                self.counters.note_kv_read(0, 1)  # real emission only
        return deltas

    def _drain_block(self) -> List[TokenDelta]:
        """Read the unread block call, if any, now."""
        call, self._block_unread = self._block_unread, None
        return [] if call is None else self._read_block(call)

    @engine_thread_only
    def drain_block_call(self) -> None:
        """For what touches requests or the cache between iterations
        (`cancel`, the block transfers, `clear_prefix_cache`,
        `embed_tokens`, shutdown): host bookkeeping is exact when they
        run, and the next `step()` hands out the tokens read here."""
        self._block_held.extend(self._drain_block())

    def _block_keys(self, live, bucket: int) -> np.ndarray:
        """Raw uint32 key data [bucket, 2] for a sampled block call: a
        fresh key a row, a seeded request's own (the program folds the
        token index in, so a seeded stream depends on the seed and the
        index alone)."""
        self._rng, sub = jax.random.split(self._rng)
        key_data = np.array(jax.random.key_data(
            jax.random.split(sub, bucket)))  # copy: jax views are RO
        for i, req in enumerate(live):
            if req.sampling.seed is not None:
                key_data[i] = np.asarray(jax.random.key_data(
                    jax.random.key(req.sampling.seed)))
        return key_data

    @staticmethod
    def _moe_report(load) -> dict:
        """A step program's third output as a report: the dict a program
        built with `moe_aux` gives ({load, touched, routing}), or the bare
        [E+1] load of one built without (the sharded builders)."""
        return load if isinstance(load, dict) else {"load": load}

    def _moe_packed_rows(self, tokens: int) -> int:
        """Rows of the packed buffer one expert layer-forward over `tokens`
        token rows hands the grouped kernel: the program's static shape
        (`ops/moe.py::moe_grouped`), so no device read.  0 where the expert
        path does not pack (dense, dispatch)."""
        if getattr(self, "_moe_mode", "dense") != "grouped":
            return 0
        from dynamo_tpu.ops.pallas.moe_grouped import (
            grouped_block_rows, packed_rows)

        cfg = self.config.model
        S = tokens * cfg.num_experts_per_token
        held = cfg.experts_local[1]
        # A share's buffer holds one more group: the other chips' rows.
        groups = held + (cfg.experts_held is not None)
        return packed_rows(S, groups,
                           grouped_block_rows(S, cfg.num_experts, held))

    def _note_moe_dev(self, load, touched, layers: int, tokens: int,
                      decode: bool = False) -> None:
        """Add one program's expert-layer report to the device-side
        accumulators (no sync): its [E+1] load, the distinct experts it
        touched and how many expert layers it ran, each over `tokens` token
        rows.  `decode`: a causal decode window or single step, tallied a
        second time on its own."""
        self._load_dev = (load if self._load_dev is None
                          else self._load_dev + load)
        if self._capture_tally is not None and touched is not None \
                and self.counters.trace_phases:
            # Dispatched while a device capture runs: tallied a second time
            # on their own (what a trace's kernel time is divided by), on
            # the device like the rest and read at the same sync.
            row = 0 if decode else 1
            acc = (jnp.zeros((2, 2), jnp.int32)
                   if self._moe_capture_dev is None
                   else self._moe_capture_dev)
            self._moe_capture_dev = self._capture_tally(acc, load, touched,
                                                        row)
            self._moe_capture_layers[row] += layers
        if touched is not None:
            self._touched_dev = (touched if self._touched_dev is None
                                 else self._touched_dev + touched)
            if decode:
                self._touched_decode_dev = (
                    touched if self._touched_decode_dev is None
                    else self._touched_decode_dev + touched)
                self._moe_decode_layers_pending += layers
        self._moe_layers_pending += layers
        self._moe_packed_pending += layers * self._moe_packed_rows(tokens)

    def _take_moe_dev(self) -> tuple:
        """Hand over the device-side accumulators and what they cover, and
        start them afresh: ((load, touched, decode touched, capture tally)
        device values or None, (expert layers, decode expert layers, packed
        rows, the capture tally's (decode, prefill) expert layers))."""
        out = ((self._load_dev, self._touched_dev, self._touched_decode_dev,
                self._moe_capture_dev),
               (self._moe_layers_pending, self._moe_decode_layers_pending,
                self._moe_packed_pending, tuple(self._moe_capture_layers)))
        self._moe_capture_dev = None
        self._moe_capture_layers = [0, 0]
        self._load_dev = self._touched_dev = self._touched_decode_dev = None
        self._moe_layers_pending = self._moe_decode_layers_pending = 0
        self._moe_packed_pending = 0
        return out

    def _fold_moe_stats(self, load, touched, layers: int,
                        decode=(None, 0), packed: int = 0,
                        capture=(None, (0, 0))) -> None:
        """Fold fetched expert-layer accumulators into the host tallies:
        what `layers` expert layers reported, through `packed` rows of
        packed buffer; `decode` = (distinct experts, expert layers) of the
        causal decode calls among them; `capture` = (the [2, 2] tally of
        the calls dispatched inside a device capture, their expert
        layers)."""
        if capture[0] is not None:
            self.counters.note_moe_capture(np.asarray(capture[0]),
                                           capture[1])
        stats = np.asarray(load, dtype=np.int64)
        self.expert_load += stats[:-1]
        self.moe_dropped_tokens += int(stats[-1])
        self._moe_unpublished = True
        if self.config.model.experts_held is not None:
            first, count = self.config.model.experts_held
            self.counters.moe_local_assignments += int(
                stats[first:first + count].sum())
        self.counters.note_moe(
            int(stats[:-1].sum()),
            int(touched) if touched is not None else 0, layers,
            int(decode[0]) if decode[0] is not None else 0, decode[1],
            packed_rows=packed)

    def _flight_recompile(self, key) -> None:
        """EngineStepCounters first-seen-shape hook: a compile is
        imminent — leave a breadcrumb naming the program and shape (cold
        misses included: a crash during warmup is exactly when you want
        to know what was compiling).  Off the steady path by
        construction (fires only on cache misses).  The compile stamp
        runs regardless of recording: the stall watchdog widens its
        threshold while a step is legitimately stuck inside XLA."""
        self.flight.note_compile()
        if self.flight.enabled:
            self.flight.record("recompile", tag=str(key[0]),
                               sig=repr(key[1:]))

    def _stored(self, jitted, name: str, **extra):
        """A meshless step program (params, cache, small arguments...)
        served from `config.program_store`; `jitted` itself without one.
        The build key spells out every argument the builders of these
        programs close over: one that is missing here would let an engine
        load a program built for another."""
        build = dict(self._program_family(), **extra)
        return program_store.stored(
            jitted, name, json.dumps(build, sort_keys=True),
            self.config.program_store, fixed_argnums=2)

    def _program_family(self) -> dict:
        """The part of the build key that every stored program of this
        engine shares: what the store's read-ahead finds them by."""
        cfg = self.config
        return dict(
            model=repr(cfg.model), block_size=self.block_size,
            decode_window=cfg.decode_window,
            use_pallas_decode=bool(self._use_pallas),
            moe_mode=self._moe_mode, with_expert_load=self._moe,
            kv_quant=self.cache_cfg.quantized,
            cache_dtype=str(jnp.dtype(self.cache_cfg.dtype)))

    def join_read_ahead(self) -> None:
        """For whoever reports this engine ready to serve, right before:
        the store's read-ahead ends here (program_store.py), so that no
        stored program is loaded, on any thread, under a served request."""
        program_store.join_read_ahead(self.config.program_store,
                                      (self.params, self.cache))

    def _harvest_program(self, first_seen: bool, tag: str, sig: tuple,
                         fn, args: tuple) -> None:
        """Feed the device-profiler's cost registry on a first-seen
        shape (note_dispatch returned True): `fn.lower(*args)` traces
        without executing or donating, so the harvest is safe right
        before the real dispatch compiles the same program.  Off the
        steady path by construction — first_seen is False on every
        warm dispatch and the call degrades to one branch."""
        if first_seen and self.profiler.enabled:
            self.profiler.harvest(tag, sig, fn, args)

    @hot_path
    def _flight_counters(self) -> None:
        """Cumulative EngineStepCounters breadcrumb (pre-computed host
        ints only — DL006); the dump reader diffs consecutive events for
        per-interval deltas."""
        c = self.counters
        self.flight.record(
            "counters", step=self.step_count,
            host_syncs=c.host_syncs, recompiles=c.xla_cache_misses,
            windows=c.window_dispatches,
            singles=c.single_step_dispatches,
            prefills=c.prefill_dispatches, spec=c.spec_dispatches,
            uploads=c.h2d_uploads)

    def _has_prefill_backlog(self) -> bool:
        return bool(self.scheduler.waiting) or any(
            r.state is RequestState.PREFILL for r in self.scheduler.running)

    def _chunk_rides(self, batch: Optional[PrefillBatch],
                     windows: float = 1.0) -> bool:
        """The one rule of mixed prefill, asked once for every dispatched
        window (`windows` 1; 1 / decode_window for a single step of a
        cohort that is leaving window mode): may `batch`, the planned
        chunk, ride behind it?

        The window earns chunks `(1 - DECODE_SHARE) / DECODE_SHARE` of
        `window_s`, the measured seconds of a plain window, as credit,
        capped at the dearest chunk measured so that an idle stretch
        banks no burst.  The chunk rides when the credit covers `chunk_s`
        of its token bucket, and pays it.  A bucket never measured rides
        once there is no debt, to be measured, pays the cap (which may
        leave a debt) and does not ride again while a window that has
        such a chunk before it is unread.  Neither the rows decoding nor the
        tokens in the chunk play a part: a window and a chunk each cost
        what they were measured to cost.

        Without a clock (the hosts of a multihost engine must decide
        alike, and a wall clock does not; or no plain window has been
        measured yet) a chunk rides behind every second window."""
        c = self.counters
        w = c.window_s
        key = cost = None
        if batch is not None:
            key = self.scheduler.config.bucket_for_packed(
                sum(i.length for i in batch.items))
            cost = c.chunk_s.get(key)
        if self._mh or w is None:
            ride = self._chunk_rode = (batch is not None
                                       and not self._chunk_rode)
        else:
            cap = max(c.chunk_s.values(), default=w)
            credit = min(cap, self._chunk_credit_s + windows * w
                         * (1.0 - DECODE_SHARE) / DECODE_SHARE)
            ride = batch is not None and credit >= (cost or 0.0) and (
                cost is not None
                or not any(e["chunk"] == key for e in self._inflight))
            if ride:
                credit -= cap if cost is None else cost
            self._chunk_credit_s = credit
        if ride:
            self._chunk_key = key
        return ride

    @hot_path
    def _window_work(self, plan) -> Optional[DecodeWork]:
        """Decode work for the window path this iteration, or None when
        the engine must leave (or drain) window mode.

        The window COHORT is the request set of the in-flight dispatches.
        A request that finishes prefill mid-flight JOINS it at a merge,
        and each merge costs one pipeline drain.  Joining rows are those
        in the ready pool (first token settled: plan.decode minus cohort)
        and those whose first token is still in flight
        (`_pending_first`): the host knew they were coming when it
        dispatched their last chunk.  One rule decides for both: once the
        joining rows are a quarter of the cohort (one row, for a cohort
        under 8), or no prompt is left to prefill, this returns None and
        `step()` drains, settles and merges; a window of the old cohort
        dispatched now would only stand in front of the new rows on the
        device queue.  Below that, windows keep going and the rows
        collect, so a large cohort does not pay a pipeline gap for each
        completion."""
        if not self._window_eligible(plan):
            return None
        reqs = [r for r in plan.decode.requests
                if r.request_id not in self._pending_first]
        if not reqs:
            return None
        if self._inflight:
            by_id = {r.request_id: r for r in reqs}
            rids = self._inflight[-1]["rids"]
            cohort = [by_id[rid] for rid in rids if rid in by_id]
            if len(cohort) != len(rids):
                # A cohort member finished/preempted: the in-flight lag
                # tensors have the old row width — drain, then remerge.
                return None
        else:
            cohort = reqs  # pipeline empty: merge everything settled
        joining = len(plan.decode.requests) - len(cohort)
        if joining and (joining >= max(1, len(cohort) // 4)
                        or not self._has_prefill_backlog()):
            return None  # hold: drain, settle, merge (step())
        if len(cohort) == len(plan.decode.requests):
            return plan.decode
        bs = self.block_size
        return DecodeWork(
            requests=cohort,
            bucket=self.scheduler.config.bucket_for_decode(len(cohort)),
            pages=self.scheduler.config.bucket_for_pages(max(
                (r.context_len + bs - 1) // bs for r in cohort)),
        )

    @hot_path
    def _settle_first_tokens(self, deltas: List[TokenDelta],
                             block: bool) -> None:
        """Collect asynchronously-sampled prefill first tokens.  `block`
        forces resolution (the single-step path must not run with
        unsettled requests)."""
        if not self._pending_batches:
            return
        enter = self.counters.enter
        enter(PHASE_SETTLE_FIRST)
        remaining = []
        for fut, reqs in self._pending_batches:
            stalls = not fut.done()
            if stalls:
                if not block:
                    remaining.append((fut, reqs))
                    continue
                self.counters.host_syncs += 1  # engine thread stalls here
                enter(PHASE_WAIT_DEVICE)
            # dynamo-lint: disable=DL001 counted sync (host_syncs above)
            toks, lps = fut.result()
            if stalls:
                enter(PHASE_SETTLE_FIRST)
            for j, req in enumerate(reqs):
                self._pending_first.discard(req.request_id)
                if (req.request_id not in self._requests
                        or req.state is not RequestState.DECODE):
                    continue  # finished/cancelled while in flight
                self._publish_completed_blocks(req)
                deltas.append(self._append_token(
                    req, int(toks[j]),
                    float(lps[j]) if lps is not None else None))
        self._pending_batches = remaining

    # -- speculative decoding (draft-k, verify-batched) ---------------------

    def _spec_eligible(self, plan) -> bool:
        # logprobs requests take the plain path: the spec accept loop
        # doesn't thread per-token logprobs (the API contract must not
        # change with a server-side perf flag).  UNSEEDED stochastic
        # rows ARE eligible: speculative_verify's rejection-sampling
        # fallback keeps their output distribution exactly `sample`'s.
        # SEEDED stochastic rows are not: their documented contract is
        # "stream depends only on (seed, token index)", and a burst
        # drawn jointly through accept/reject chains depends on step
        # boundaries and draft content — only the plain per-token path
        # can honor the seed guarantee.
        #
        # Mesh-level eligibility is `_spec_capable` (the capability
        # table, ONE source of truth — pp/multihost are declared
        # impossible there and already rejected at construction;
        # dp-attention locality composes since ISSUE 12 leg 5: the
        # verify batch resolves rows to their slots).
        return (self.config.speculative_tokens > 0
                and self._spec_capable
                and plan.decode is not None
                and plan.prefill is None
                and not self.scheduler.waiting
                and all(not r.sampling.logprobs
                        and not (r.sampling.temperature > 0
                                 and r.sampling.seed is not None)
                        for r in plan.decode.requests))

    def _spec_verify_fn(self):
        """Lazily-jitted batched verify (sampling.speculative_verify):
        accept/resample runs on device, ONE host sync fetches
        (emitted [B, K+1], n_emit [B]) instead of [B, T, V] logits.
        `greedy_only` is static — the all-greedy serving case compiles
        to an argmax chain with no sort/softmax/categorical."""
        if self._spec_verify is None:
            from dynamo_tpu.engine.sampling import speculative_verify

            self._spec_verify = jax.jit(
                speculative_verify, static_argnames=("greedy_only",))
        return self._spec_verify

    def _row_keys(self, reqs, n: int, rows=None):
        """Per-row sampling keys, ONE discipline for the plain and spec
        paths: one fresh split per step for unseeded rows; seeded rows
        overwritten with fold_in(seed, emitted-token index) so a seeded
        stream depends only on (seed, token index).  (The spec path
        never sees seeded stochastic rows — _spec_eligible routes them
        to the plain path, the only one that can honor that contract.)
        `rows`: device row per request when requests don't sit at
        compact indices (slot-pinned dp-attention locality)."""
        self._rng, sub = jax.random.split(self._rng)
        keys = jax.random.split(sub, n)
        for i, r in enumerate(reqs):
            if r.sampling.seed is not None:
                keys = keys.at[rows[i] if rows is not None else i].set(
                    jax.random.fold_in(
                        jax.random.key(r.sampling.seed),
                        r.sampling.seed_offset + r.prior_output
                        + len(r.output_tokens)))
        return keys

    def _run_decode_spec(self, work: DecodeWork) -> Optional[List[TokenDelta]]:
        """One speculative step: feed [last_token, draft_0..draft_{k-1}]
        as a T=k+1 chunk, get logits at every position, and accept the
        longest draft prefix the model agrees with — up to k+1 tokens
        per device step (the +1 is the model's own token at the first
        disagreement / the bonus after a full accept, which costs
        nothing extra).  Accept/resample semantics live in
        sampling.speculative_verify (greedy = argmax chain, stochastic =
        rejection sampling).

        KV rollback for rejected positions is the overwrite discipline:
        a rejected draft's KV row sits at a position the request's NEXT
        fed token rewrites before anything attends to it (growth is
        monotonic and context gathers mask positions >= seq_len), so no
        explicit scrub pass is needed — the accounting below only ever
        advances context_len by the ACCEPTED count.

        Returns None when capacity can't cover the lookahead (caller
        falls back to the plain path, which preempts properly) or no row
        produced a draft (a (K+1)-wide forward to emit ~1 token per row
        is strictly worse than the plain step)."""
        enter = self.counters.enter
        enter(PHASE_SINGLE_STEP)
        K = self.config.speculative_tokens
        T = K + 1
        reqs = work.requests
        # Compact-row-aware verify (ISSUE 12 leg 5): under dp-attention
        # locality a request's rows are pinned to its SLOT (its pages
        # live on the slot's shard), so the verify batch resolves each
        # request to the owning shard's slot range instead of compact
        # order — same row discipline as _run_decode.
        bucket = (self._dp_rows if self._dp_local
                  else self._pad_rows(work.bucket))
        rows = [self._decode_row(r, j) for j, r in enumerate(reqs)]

        vocab = self.config.model.vocab_size
        drafts = []
        draft_lens = []  # tokens the drafter REALLY proposed per row
        for req in reqs:
            if not self.scheduler.ensure_capacity(req, req.context_len + T):
                return None
            hist = req.prompt_tokens[: req.prefilled] + req.output_tokens
            d = []
            for t in self._drafter.propose(hist, K)[:K]:
                # Custom drafters are untrusted: an out-of-range id
                # would silently clamp in the embedding gather AND in
                # the verify's probability lookup, and could then be
                # STREAMED to the client.  Truncate at the first bad id
                # (the suffix after it is conditioned on garbage).
                if not 0 <= int(t) < vocab:
                    break
                d.append(int(t))
            draft_lens.append(len(d))
            drafts.append((d + [0] * K)[:K])
        if not any(draft_lens):
            return None

        bs = self.block_size
        width = self.scheduler.config.bucket_for_pages(
            max((r.context_len + T + bs - 1) // bs for r in reqs))
        tokens = np.zeros((bucket, T), np.int32)
        positions = np.full((bucket, T), self._pad_position, np.int32)
        seq_lens = np.zeros((bucket,), np.int32)
        bts = np.zeros((bucket, width), np.int32)
        temp = np.zeros((bucket,), np.float32)
        top_k = np.zeros((bucket,), np.int32)
        top_p = np.ones((bucket,), np.float32)
        draft_arr = np.zeros((bucket, K), np.int32)
        self._mark_decode(reqs)
        for i, req in enumerate(reqs):
            row = rows[i]
            ctx = req.context_len
            last = (req.output_tokens[-1] if req.output_tokens
                    else req.prompt_tokens[-1])
            tokens[row] = [last] + drafts[i]
            positions[row] = np.arange(ctx - 1, ctx - 1 + T)
            seq_lens[row] = ctx + K  # every fed token's KV is written
            n = min(len(req.pages), width)
            bts[row, :n] = req.pages[:n]
            temp[row] = req.sampling.temperature
            top_k[row] = req.sampling.top_k
            top_p[row] = req.sampling.top_p
            draft_arr[row] = drafts[i]

        # sample_positions=None → logits at EVERY chunk position [B,T,V].
        first = self.counters.note_dispatch("spec", bucket, T, width)
        self.counters.spec_dispatches += 1
        fl = self.flight
        if fl.enabled:
            fl.record("spec", bucket=bucket, chunk=T, width=width)
        # Effective-bytes model: ONE sweep of each row's KV serves up to
        # T emitted tokens (tokens tally added below from n_emit);
        # per-chip bytes under meshes (kv_shard_count).
        self.counters.note_kv_read(
            sum(r.context_len + K for r in reqs)
            * self._ctx_token_bytes_chip, 0)
        tok_d = jnp.asarray(tokens)
        pos_d = jnp.asarray(positions)
        sl_d = jnp.asarray(seq_lens)
        bts_d = jnp.asarray(bts)
        self._harvest_program(
            first, "spec", (bucket, T, width), self._step,
            (self.params, self.cache, tok_d, pos_d, sl_d, bts_d, None))
        logits, self.cache = self._run_step(
            tok_d, pos_d, sl_d, bts_d, None)
        emitted_dev, n_emit_dev = self._spec_verify_fn()(
            logits, jnp.asarray(draft_arr), jnp.asarray(temp),
            jnp.asarray(top_k), jnp.asarray(top_p),
            self._row_keys(reqs, bucket, rows=rows),
            greedy_only=all(r.sampling.temperature <= 0 for r in reqs))
        self.counters.host_syncs += 1
        enter(PHASE_WAIT_DEVICE)
        emitted, n_emit = jax.device_get((emitted_dev, n_emit_dev))
        enter(PHASE_EMIT)
        emitted = np.asarray(emitted)
        n_emit = np.asarray(n_emit)

        deltas: List[TokenDelta] = []
        stats = self.metrics.spec_decode_stats
        for i, req in enumerate(reqs):
            n = int(n_emit[rows[i]])
            appended = 0
            for tok in emitted[rows[i], :n]:
                if req.request_id not in self._requests:
                    break  # finished mid-burst (stop token / max_tokens)
                self._publish_completed_blocks(req)
                deltas.append(self._append_token(req, int(tok)))
                appended += 1
            # Telemetry counts what actually reached the output stream —
            # a request finishing mid-burst discards the tail, and
            # phantom tokens would understate effective-bytes and
            # inflate the reported acceptance rate.  The denominator is the
            # tokens the drafter REALLY proposed (draft_lens), not the
            # zero-padded K — a drafter that honestly proposes 1 token
            # per step at K=4 would otherwise read as 25% acceptance
            # (tests/test_spec_decode.py holds the rate at >= 0.6).
            self.counters.note_kv_read(0, appended)
            if draft_lens[i] and stats is not None:
                stats.num_spec_tokens += draft_lens[i]
                stats.num_drafts += draft_lens[i]
                used_accepts = min(n - 1, appended, draft_lens[i])
                stats.num_accepted_tokens += used_accepts
                per_pos = stats.num_accepted_tokens_per_pos
                while len(per_pos) < K:
                    per_pos.append(0)
                for j in range(used_accepts):
                    per_pos[j] += 1
        return deltas

    def _window_eligible(self, plan) -> bool:
        # Speculative decoding (when configured) supersedes windows.
        # (Prefill work / waiting admissions do NOT disqualify windows:
        # bounded prefill chunks dispatch concurrently behind them —
        # see step().  MoE windows thread the expert-load aux through
        # the loop carry since r5; pp meshes ride the schedule-looping
        # window program since ISSUE 12 leg 3.)
        if not (self.config.decode_window > 1
                and self.config.speculative_tokens == 0
                and plan.decode is not None):
            return False
        # Logprob requests take the single-step path too (the window's
        # fori_loop doesn't thread the per-token logprob aux).
        if any(r.sampling.logprobs for r in plan.decode.requests):
            return False
        # End-of-life guard: if every request's remaining budget is under
        # half a window (beyond what in-flight windows already cover), a
        # dispatch would be mostly discarded tokens and the single-step
        # path is strictly cheaper (a max_tokens=1 fleet through windows
        # costs K steps per useful token).  Stop-token finishes are
        # unpredictable; the max_tokens bound is the static one.
        lookahead = len(self._inflight) * self.config.decode_window
        return any(
            (r.sampling.max_tokens - r.prior_output - len(r.output_tokens)
             - lookahead) > self.config.decode_window // 2
            for r in plan.decode.requests)

    def _collect_dead(self, deltas: List[TokenDelta]) -> None:
        for rid, req in list(self._requests.items()):
            if req.state is RequestState.FINISHED and req.finish_reason is not None:
                deltas.append(TokenDelta(
                    request_id=rid, token_ids=[], finished=True,
                    finish_reason=req.finish_reason))
                self._drop(req)

    def _refresh_metrics(self) -> None:
        ws = self.metrics.worker_stats
        ws.request_active_slots = len(self.scheduler.running)
        ws.num_requests_waiting = len(self.scheduler.waiting)
        ks = self.metrics.kv_stats
        ks.kv_active_blocks = (self.allocator.num_blocks - 1
                               - self.allocator.free_blocks)
        ks.gpu_cache_usage_perc = self.allocator.usage
        # Real-engine prefix-cache hit rate (the mocker reported this
        # from day one; the real engine was dark): fraction of admitted
        # prompt tokens whose prefill the cache skipped, from the
        # scheduler's admission-time match accounting.  Host ints only.
        matched = self.scheduler.prefix_hit_tokens
        total = matched + self.scheduler.prefix_miss_tokens
        ks.gpu_prefix_cache_hit_rate = matched / total if total else 0.0
        if self._ssm:
            self.counters.ssm_slots_used = len(self.scheduler.running)
        if self._window:
            self.counters.window_pool["used"] = (
                self.cache_cfg.window_blocks - 1
                - self.window_allocator.free_blocks)
            self.counters.window_pool["full_used"] = (
                self.allocator.num_blocks - 1 - self.allocator.free_blocks)
            self.counters.window_blocks_released = \
                self.scheduler.window_released
        if self._moe and (
                self.step_count % 32 == 0
                or ((self._load_dev is not None or self._moe_unpublished)
                    and not self.scheduler.running
                    and not self.scheduler.waiting)):
            # Periodic (not per-step: each snapshot syncs the device) —
            # plus a drain-edge sync, else a worker whose requests all
            # finish in < 32 steps never publishes its expert load and
            # /metrics stays dark until the next burst.
            self.metrics.expert_load = [
                int(x) for x in self.snapshot_expert_load()]
            self._moe_unpublished = False
            self.metrics.moe_dropped_tokens = self.moe_dropped_tokens

    # -- internals --------------------------------------------------------

    def _pad_rows(self, n: int) -> int:
        m = self._row_mult
        return -(-n // m) * m

    def _slot_rows(self, n: int, reqs=(), rows=None) -> np.ndarray:
        """[n] state slots for a program's rows or segments: `reqs[j]`'s
        slot at index `rows[j]` (default j), the scratch slot elsewhere."""
        slots = np.full((n,), self.cache_cfg.state_slots, np.int32)
        for j, req in enumerate(reqs):
            slots[j if rows is None else rows[j]] = req.slot
        return slots

    def _window_rows(self, n: int, width: int, reqs=(),
                     rows=None) -> np.ndarray:
        """[n, width] window-group tables for a program's rows or segments:
        `reqs[j]`'s at index `rows[j]` (default j), the null block
        elsewhere and where a page went back to its pool."""
        tables = np.zeros((n, width), np.int32)
        for j, req in enumerate(reqs):
            m = min(len(req.window_pages), width)
            tables[j if rows is None else rows[j], :m] = req.window_pages[:m]
        return tables

    def _state_args(self, n: int, reqs=(), rows=None, width: int = 0
                    ) -> tuple:
        """What a step program takes after its other arguments: the rows'
        state slots (a model with state-space layers) or their window-group
        tables, `width` pages wide (a model with window layers), on the
        device; nothing for any other model."""
        if self._window:
            return (self._dev(self._window_rows(n, width, reqs, rows)),)
        if not self._ssm:
            return ()
        return (self._dev(self._slot_rows(n, reqs, rows)),)

    def _window_read_bytes(self, spans) -> int:
        """Modeled bytes the window layers' decode attention reads, beside
        the full layers' (`_ctx_token_bytes_chip` a context token): `spans`
        (context, steps) a row, each step reading min(context, window)
        tokens of every window layer."""
        if not self._window:
            return 0
        w = self.counters.attn_window
        per_token = (self.cache_cfg.window_bytes_per_block
                     // self.block_size)
        return per_token * sum(
            min(ctx + i, w) for ctx, steps in spans for i in range(steps))

    def _window_tables_last(self, fn):
        """Step programs take their arguments by position: a model with
        window layers hands its rows' window-group tables last."""
        if not self._window:
            return fn

        # Under the wrapped function's name: XLA names a program after its
        # function (`jit_run`, `jit_step`), and a capture's reduction tells
        # the decode window from the prefill chunk by that name.
        @functools.wraps(fn)
        def by_position(*args):
            return fn(*args[:-1], window_tables=args[-1])

        return by_position

    def _run_step(self, tokens, positions, seq_lens, bts, sample_pos,
                  items=None, state=()):
        """One device step; accumulates the MoE expert-load aux (when
        present) ON DEVICE — a per-step device_get here would cost a
        host↔device round-trip per step.  `snapshot_expert_load()` syncs
        on demand (metrics pump cadence).  `items`: the chunks of a padded
        prefill call (row i holds chunk i), for a recording."""
        out = self._step(self.params, self.cache, tokens, positions,
                         seq_lens, bts, sample_pos, *state)
        if self._moe:
            logits, cache, load = out
            aux = self._moe_report(load)
            self._note_moe_dev(aux["load"], aux.get("touched"),
                               self.config.model.num_moe_layers,
                               tokens.size)
            if (self.block_record is not None and items is not None
                    and "routing" in aux):
                T = tokens.shape[1]
                self._record_prefill(items, aux["routing"],
                                     [i * T for i in range(len(items))])
            return logits, cache
        return out

    def snapshot_expert_load(self) -> Optional[np.ndarray]:
        """Cumulative per-expert assignment counts (None for dense
        models).  Syncs the device [E+1] stats accumulator once per
        call, splitting it into the per-expert load vector and the
        dropped-assignments counter (`moe_dropped_tokens`)."""
        if not self._moe:
            return None
        if self._load_dev is not None:
            self.counters.host_syncs += 1
            self.counters.enter(PHASE_WAIT_DEVICE)
            ((load, touched, dec, cap),
             (layers, dec_layers, packed, cap_layers)) = self._take_moe_dev()
            stats = np.asarray(self._fetch_host(load), dtype=np.int64)
            touched = (None if touched is None
                       else self._fetch_host(touched))
            dec = None if dec is None else self._fetch_host(dec)
            cap = None if cap is None else self._fetch_host(cap)
            self.counters.enter(PHASE_DELIVER)
            self._fold_moe_stats(stats, touched, layers, (dec, dec_layers),
                                 packed, (cap, cap_layers))
        return self.expert_load

    def _sp_eligible(self, batch: PrefillBatch) -> bool:
        """Ring-SP prefill handles FULL prompts (no prior cached context
        is read — ops/ring_attention.py); route the batch through the
        ring when every item is a whole prompt past the threshold."""
        if self._sp_step is None:
            return False
        thr = self.config.sp_prefill_threshold
        return all(
            w.start == 0 and w.length == len(w.request.prompt_tokens)
            and w.length >= thr
            for w in batch.items)

    def _run_prefill_batch(self, batch: PrefillBatch,
                           async_first: bool = False) -> List[TokenDelta]:
        """One device call for ALL scheduled prefill chunks (ragged rows
        padded to the chunk bucket; pad rows/tails write to the null block).
        Completion rows sample their first output token (TTFT).

        `async_first`: sample completions without blocking — the fetch
        resolves on the pool thread and step() settles it later (mixed
        window mode must not serialize every window behind a device
        sync).  Until settled, the request sits in _pending_first and is
        excluded from decode work."""
        self.counters.enter(PHASE_DISPATCH_PREFILL)
        if (self._use_packed_prefill and not self._sp_eligible(batch)
                and not any(w.request.prompt_embeds is not None
                            for w in batch.items)):
            # Packed ragged plane (ISSUE 10): one flat token axis with
            # segment block tables through the Pallas flash-prefill
            # kernel.  Multimodal batches (input-embeds step variant)
            # and ring-SP-eligible batches keep their dedicated paths.
            return self._run_packed_prefill(batch, async_first)
        R, T, P = self._pad_rows(batch.rows), batch.chunk, batch.pages
        n_tokens = sum(w.length for w in batch.items)
        self.counters.prefill_dispatches += 1
        self.counters.prefill_tokens_dispatched += n_tokens
        self.counters.note_prefill_pairs(batch.items)
        fl = self.flight
        if fl.enabled:
            fl.record("prefill", rows=R, chunk=T, pages=P)
        tokens = np.zeros((R, T), np.int32)
        positions = np.full((R, T), self._pad_position, np.int32)
        seq_lens = np.zeros((R,), np.int32)
        bts = np.zeros((R, P), np.int32)

        sample_pos = np.zeros((R,), np.int32)
        for i, work in enumerate(batch.items):
            req = work.request
            chunk = req.prompt_tokens[work.start: work.start + work.length]
            tokens[i, : work.length] = chunk
            positions[i, : work.length] = np.arange(
                work.start, work.start + work.length)
            seq_lens[i] = work.start + work.length
            sample_pos[i] = work.length - 1
            n = min(len(req.pages), P)
            bts[i, :n] = req.pages[:n]

        mm_items = [w for w in batch.items
                    if w.request.prompt_embeds is not None]
        sp_elig = self._sp_eligible(batch)
        # The sp / multimodal / plain branches are distinct compiled
        # programs — the shape signature must not collide across them.
        first = self.counters.note_dispatch(
            "prefill", R, T, P, bool(mm_items), sp_elig)
        prefill_sig = (R, T, P, bool(mm_items), sp_elig)
        if sp_elig:
            # Served long-context path: whole-prompt prefill over the ICI
            # ring, T sharded over sp (VERDICT r3 next-4 — the ring was
            # test-only before; now EngineCore routes real requests
            # through it).
            self.sp_prefill_count += len(batch.items)
            # Modeled per-chip ring traffic: each chip's resident chunk
            # (T/sp tokens) rides (sp−1) hops per layer; the payload per
            # token comes from the ONE cache-mode-aware accounting
            # (ring_payload_bytes_per_token), so the series halves under
            # int8 exactly like the decode read series does.
            sp = self.mesh.shape["sp"]
            # PATH-INDEPENDENT by construction: the Pallas flash ring
            # moves exactly the rows+scales the XLA ppermute ring moves
            # (same per-token payload, same sp-1 hops), so the modeled
            # series is charged before the path split and can never
            # fork between them.
            self.counters.note_ring_exchange(
                sum(w.length for w in batch.items)
                * self.cache_cfg.ring_payload_bytes_per_token
                * (sp - 1) // sp)
            if self._sp_pallas:
                # Kernel-path attribution via the SAME predicate the
                # trace-time dispatch uses (shapes are static there),
                # so this host counter can never disagree with the
                # compiled program about which ring ran.
                from dynamo_tpu.ops.pallas.ring_attention import (
                    ring_kernel_supported)

                cfg = self.config.model
                tp = self.mesh.shape["tp"]
                feat = cfg.num_kv_heads * cfg.head_dim // max(tp, 1)
                dp = self.mesh.shape["dp"]
                if ring_kernel_supported(
                        feat, T // sp, max(R // dp, 1),
                        cfg.num_heads // max(tp, 1), cfg.head_dim,
                        jax.default_backend() != "tpu"):
                    self.counters.ring_kernel_prefills += len(batch.items)
            sp_args = (self.params, self.cache, self._dev(tokens),
                       self._dev(positions), self._dev(seq_lens),
                       self._dev(bts), self._dev(sample_pos))
            self._harvest_program(first, "prefill", prefill_sig,
                                  self._sp_step, sp_args)
            logits, self.cache = self._sp_step(*sp_args)
        elif mm_items:
            # Multimodal prefill: chunk positions inside a request's
            # embedding span take the provided vision embeddings instead
            # of token lookups (llm/multimodal.py).
            H = self.config.model.hidden_size
            embeds = np.zeros((R, T, H), np.float32)
            mask = np.zeros((R, T), bool)
            for i, work in enumerate(batch.items):
                pe = work.request.prompt_embeds
                if pe is None:
                    continue
                lo = work.start
                hi = min(work.start + work.length, pe.shape[0])
                if hi > lo:
                    embeds[i, : hi - lo] = pe[lo:hi]
                    mask[i, : hi - lo] = True
            if self._mm_step is None:
                if self.mesh is not None:
                    from dynamo_tpu.parallel.sharding import (
                        make_sharded_mm_step)

                    self._mm_step = make_sharded_mm_step(
                        self.config.model, self.block_size, self.mesh,
                        dp_attention=self.config.dp_attention,
                        dp_local=self._dp_local,
                        kv_quant=self.cache_cfg.quantized)
                else:
                    self._mm_step = jax.jit(
                        make_forward_step(self.config.model,
                                          self.block_size,
                                          with_input_embeds=True),
                        donate_argnums=(1,))
            mm_args = (self.params, self.cache, self._dev(tokens),
                       self._dev(positions), self._dev(seq_lens),
                       self._dev(bts), self._dev(sample_pos),
                       self._dev(embeds), self._dev(mask))
            self._harvest_program(first, "prefill", prefill_sig,
                                  self._mm_step, mm_args)
            logits, self.cache = self._mm_step(*mm_args)
        else:
            tok_d = self._dev(tokens)
            pos_d = self._dev(positions)
            sl_d = self._dev(seq_lens)
            bts_d = self._dev(bts)
            smp_d = self._dev(sample_pos)
            state = self._state_args(R, [w.request for w in batch.items],
                                     width=P)
            if self._ssm:
                self.counters.note_ssm_prefill(batch.items)
            if self._window:
                self.counters.note_attn_pairs(
                    "prefill", [(w.start, w.length) for w in batch.items])
            self._harvest_program(
                first, "prefill", prefill_sig, self._step,
                (self.params, self.cache, tok_d, pos_d, sl_d, bts_d,
                 smp_d) + state)
            logits, self.cache = self._run_step(
                tok_d, pos_d, sl_d, bts_d, smp_d, items=batch.items,
                state=state)

        return self._finish_prefill_items(batch.items, logits, async_first)

    def _finish_prefill_items(self, items, logits,
                              async_first: bool) -> List[TokenDelta]:
        """Shared prefill completion tail (padded and packed planes):
        advance scheduler state, seal blocks, and sample first tokens
        for rows whose prompt completed — row i of `logits` belongs to
        items[i] on both planes (padded rows / packed segments)."""
        deltas: List[TokenDelta] = []
        done_rows: List[int] = []
        for i, work in enumerate(items):
            self.scheduler.prefill_done(work)
            self._publish_completed_blocks(work.request)
            if work.request.state is RequestState.DECODE:
                done_rows.append(i)
        if self._diffusion:
            # Nothing is sampled from a prefill: a position's logits
            # predict that position, and the first generated block gets
            # its own forwards.
            return deltas
        if done_rows:
            # Sample first tokens for rows whose prompt completed (logits
            # already point at each row's last real chunk position).
            sel = self._select_rows(logits, done_rows)
            reqs = [items[i].request for i in done_rows]
            dispatched = self.counters.decode_dispatches
            for req in reqs:
                req.decode_dispatches_at_prefill = dispatched
            if async_first:
                fut = self._sample_rows(sel, reqs, async_fetch=True)
                for req in reqs:
                    self._pending_first.add(req.request_id)
                self._pending_batches.append((fut, reqs))
                return deltas
            sampled, lps = self._sample_rows(sel, reqs)
            for j, req in enumerate(reqs):
                deltas.append(self._append_token(
                    req, int(sampled[j]),
                    float(lps[j]) if lps is not None else None))
        return deltas

    # -- packed ragged prefill (ISSUE 10) ----------------------------------

    def _packed_prefill_fn(self):
        """Lazily-jitted packed ragged prefill step (donated cache).
        MoE models thread the engine's resolved meshless moe_mode (the
        packed plane is meshless v1) and return a third output, the
        [E+1] expert-load stats vector (a block-diffusion model the dict
        of `moe_aux`: what its counters and a recording read)."""
        if self._packed_step is None:
            from dynamo_tpu.models.llama import make_packed_prefill_step

            self._packed_step = jax.jit(
                self._window_tables_last(make_packed_prefill_step(
                    self.config.model, self.block_size,
                    moe_mode=getattr(self, "_moe_mode", "dense"),
                    moe_aux=self._moe)),
                donate_argnums=(1,))
            if self.mesh is None:
                self._packed_step = self._stored(
                    self._packed_step, "packed_prefill")
        return self._packed_step

    def _kernels_eligible(self, feat: int) -> bool:
        """Can the paged attention kernels take this model's cache rows on
        the chip: `feat` the per-shard row width.  One rule a cache form."""
        cfg = self.config.model
        if cfg.is_latent:
            from dynamo_tpu.ops.pallas.latent_attention import (
                latent_geometry_ok)

            return latent_geometry_ok(feat, cfg.kv_lora_rank,
                                      self.block_size)
        from dynamo_tpu.ops.pallas import mosaic_geometry_ok

        return mosaic_geometry_ok(feat, self.block_size)

    def _record_decode(self, reqs, rows, routing, first=None) -> None:
        """A recording's entry for one causal decode call (a window of K
        steps, or a single step as K = 1): the experts each request's fed
        token chose in each expert layer at each step.  `routing`
        [K, L, bucket, k] stays on the device until the recording is read;
        `first[i]` the position of the token request i was fed at step 0."""
        if first is None:
            first = [r.context_len - 1 for r in reqs]
        self.block_record.append({
            "decode": True,
            "rids": [r.request_id for r in reqs],
            "rows": list(rows), "starts": list(first),
            "routing": routing})

    def _record_prefill(self, items, routing, flat_start) -> None:
        """A recording's entry for one prefill call: for each chunk, the
        experts its tokens chose in each layer.  `routing` [L, N, k] over
        the call's flat token axis, `flat_start[i]` where chunk i begins
        on it."""
        routing = np.asarray(jax.device_get(routing))
        self.block_record.append({
            "prefill": True,
            "rids": [w.request.request_id for w in items],
            "starts": [w.start for w in items],
            "routing": [routing[:, o: o + w.length]
                        for w, o in zip(items, flat_start)]})

    @hot_path
    def _run_packed_prefill(self, batch: PrefillBatch,
                            async_first: bool = False) -> List[TokenDelta]:
        """Packed ragged prefill: the scheduler's chunks pack into flat
        [T] programs (scheduler.pack_prefill_chunks sizes each pack to
        the packed token budget with PACK_ALIGN'd segment starts), each
        dispatched once through the Pallas flash-prefill kernel — no
        [rows, chunk] bucket padding, no gather materialisation."""
        from dynamo_tpu.engine.scheduler import pack_prefill_chunks
        from dynamo_tpu.ops.pallas import PACK_ALIGN

        sched = self.scheduler.config
        deltas: List[TokenDelta] = []
        for items in pack_prefill_chunks(
                batch.items, sched.packed_prefill_budget(),
                sched.packed_prefill_segments, align=PACK_ALIGN):
            deltas.extend(self._dispatch_packed_prefill(items, async_first))
        return deltas

    @hot_path
    def _dispatch_packed_prefill(self, items,
                                 async_first: bool) -> List[TokenDelta]:
        from dynamo_tpu.ops.pallas import PACK_ALIGN

        sched = self.scheduler.config
        bs = self.block_size
        R = sched.packed_prefill_segments
        aligned = sum(-(-w.length // PACK_ALIGN) * PACK_ALIGN
                      for w in items)
        T = sched.bucket_for_packed(aligned)
        P = sched.bucket_for_pages(max(
            (w.start + w.length + bs - 1) // bs for w in items))
        tokens = np.zeros((T,), np.int32)
        positions = np.full((T,), self._pad_position, np.int32)
        seg_ids = np.zeros((T,), np.int32)
        bts = np.zeros((R, P), np.int32)
        q_starts = np.zeros((R,), np.int32)
        q_lens = np.zeros((R,), np.int32)
        seq_lens = np.zeros((R,), np.int32)
        sample_pos = np.zeros((R,), np.int32)
        off = 0
        for i, work in enumerate(items):
            req = work.request
            L = work.length
            tokens[off: off + L] = req.prompt_tokens[
                work.start: work.start + L]
            positions[off: off + L] = np.arange(work.start, work.start + L)
            seg_ids[off: off + L] = i
            q_starts[i] = off
            q_lens[i] = L
            seq_lens[i] = work.start + L
            sample_pos[i] = off + L - 1
            n = min(len(req.pages), P)
            bts[i, :n] = req.pages[:n]
            off += -(-L // PACK_ALIGN) * PACK_ALIGN
        n_tokens = sum(w.length for w in items)
        self.counters.prefill_dispatches += 1
        self.counters.packed_prefill_dispatches += 1
        self.counters.prefill_tokens_dispatched += n_tokens
        self.counters.note_prefill_pairs(items)
        first = self.counters.note_dispatch("prefill_packed", T, R, P)
        fl = self.flight
        if fl.enabled:
            fl.record("prefill_packed", tokens=T, segs=R, pages=P)
        pfn = self._packed_prefill_fn()
        pargs = (self.params, self.cache, self._dev(tokens),
                 self._dev(positions), self._dev(seg_ids), self._dev(bts),
                 self._dev(q_starts), self._dev(q_lens),
                 self._dev(seq_lens), self._dev(sample_pos))
        pargs += self._state_args(R, [w.request for w in items], width=P)
        if self._ssm:
            self.counters.note_ssm_prefill(items)
        if self._window:
            self.counters.note_attn_pairs(
                "prefill", [(w.start, w.length) for w in items])
        self._harvest_program(first, "prefill_packed", (T, R, P),
                              pfn, pargs)
        res = pfn(*pargs)
        if self._moe:
            logits, self.cache, load = res
            aux = self._moe_report(load)
            # Same lazy-sync discipline as _run_step: accumulate the
            # [E+1] stats on device, snapshot on the metrics cadence.
            self._note_moe_dev(aux["load"], aux.get("touched"),
                               self.config.model.num_moe_layers, T)
            if self.block_record is not None and "routing" in aux:
                self._record_prefill(items, aux["routing"],
                                     q_starts.tolist())
        else:
            logits, self.cache = res
        return self._finish_prefill_items(items, logits, async_first)

    @engine_thread_only
    def packed_prefill_shape_set(self) -> List[Tuple[int, int, int]]:
        """The complete (packed tokens, segments, pages) lattice the
        packed plane can dispatch — small by construction (≤2 token
        buckets × the page-bucket ladder), which is what makes
        `prewarm_prefill` affordable where prewarming the padded
        rows × chunks × pages lattice never was."""
        sched = self.scheduler.config
        return [(t, sched.packed_prefill_segments, p)
                for t in sched.packed_buckets()
                for p in sched.page_bucket_ladder()]

    @engine_thread_only
    def prewarm_prefill(self) -> int:
        """Compile every packed prefill shape now (worker
        `--prewarm-prefill`), through the persistent XLA compile cache,
        so the first real request doesn't pay the cold-prefill cliff.
        All-pad dispatches (q_lens 0, null tables) — the kernel skips
        the loops but the program still compiles and caches.  Returns
        the number of shapes compiled; 0 when the packed plane is off."""
        if not self._use_packed_prefill:
            return 0
        fn = self._packed_prefill_fn()
        shapes = self.packed_prefill_shape_set()
        for (T, R, P) in shapes:
            tokens = np.zeros((T,), np.int32)
            positions = np.full((T,), self._pad_position, np.int32)
            seg_ids = np.zeros((T,), np.int32)
            zeros_r = self._dev(np.zeros((R,), np.int32))
            # note_dispatch BEFORE the dispatch: the compile stamp must
            # cover the compile it announces (watchdog grace), and the
            # first-seen harvest must run while self.cache is still
            # live — fn donates the cache buffer on the real call.
            # Prewarmed shapes land in the cost registry through the
            # same path as serving dispatches, so `--prewarm-prefill`
            # cannot create a permanently-dark program set.
            first = self.counters.note_dispatch("prefill_packed", T, R, P)
            cargs = (self.params, self.cache, self._dev(tokens),
                     self._dev(positions), self._dev(seg_ids),
                     self._dev(np.zeros((R, P), np.int32)), zeros_r,
                     zeros_r, zeros_r, zeros_r)
            cargs += self._state_args(R, width=P)
            self._harvest_program(first, "prefill_packed", (T, R, P),
                                  fn, cargs)
            # (logits, cache) and, on an expert block, its stats after.
            self.cache = fn(*cargs)[1]
        return len(shapes)

    def _decode_row(self, req: Request, compact_index: int) -> int:
        """Device row for a decoding request: its SLOT under dp-attention
        locality (rows must ride one device for the request's lifetime —
        compaction would migrate them across shards mid-stream), compact
        order otherwise."""
        return req.slot if self._dp_local else compact_index

    def _run_decode(self, work: DecodeWork) -> List[TokenDelta]:
        enter = self.counters.enter
        enter(PHASE_SINGLE_STEP)
        reqs = work.requests
        bucket = (self._dp_rows if self._dp_local
                  else self._pad_rows(work.bucket))

        tokens = np.zeros((bucket, 1), np.int32)
        positions = np.full((bucket, 1), self._pad_position, np.int32)
        seq_lens = np.zeros((bucket,), np.int32)
        bts = np.zeros((bucket, work.pages), np.int32)

        live: List[Request] = []
        rows: List[int] = []
        for req in reqs:
            # The token being fed is the last sampled one — its KV has NOT
            # been written yet.  It lands at position context_len - 1 and
            # the valid context becomes context_len (ADVICE r1: feeding at
            # context_len shifted every generated token's KV/RoPE by one).
            ctx = req.context_len
            if not self.scheduler.ensure_capacity(req, ctx):
                self._preempt_or_finish(req)
                continue
            i = self._decode_row(req, len(live))
            tokens[i, 0] = (req.output_tokens[-1] if req.output_tokens
                            else req.prompt_tokens[-1])
            positions[i, 0] = ctx - 1
            seq_lens[i] = ctx
            n = min(len(req.pages), work.pages)
            bts[i, :n] = req.pages[:n]
            live.append(req)
            rows.append(i)

        if not live:
            return []

        self._mark_decode(live)
        self.counters.single_step_dispatches += 1
        fl = self.flight
        if fl.enabled:
            fl.record("decode1", bucket=bucket, pages=work.pages)
        # Effective-bytes model: this step's attention reads each live
        # row's full KV context once (weights excluded — this series
        # isolates the KV plane the quantized cache halves); per-chip
        # bytes under meshes (kv_shard_count).
        self.counters.note_kv_read(
            sum(r.context_len for r in live)
            * self._ctx_token_bytes_chip
            + self._window_read_bytes((r.context_len, 1) for r in live),
            len(live))
        zeros = self._zeros_dev.get(bucket)
        if zeros is None:
            zeros = self._zeros_dev[bucket] = self._dev(
                np.zeros((bucket,), np.int32))
        state = self._state_args(bucket, live, rows, width=work.pages)
        if self._ssm:
            self.counters.note_ssm_decode(len(live), 1, bucket)
        if self._window:
            self.counters.note_attn_pairs(
                "decode", [(r.context_len - 1, 1) for r in live])
        if (self._fused_greedy_capable
                and all(r.sampling.temperature <= 0 for r in live)
                and not any(r.sampling.logprobs for r in live)):
            # Fused greedy single step: forward + argmax in ONE compiled
            # program (donated cache), ONE host sync for [bucket] tokens.
            # The unfused path is 3 dispatches (step, row gather, argmax)
            # plus a [B, V] f32 logits output allocation per step — the
            # r5 single-step cliff's engine-side half.  Sharded engines
            # fuse through make_sharded_step(plane.fused), pp through the
            # all-in-one stage program (make_pp_greedy_step), and the
            # lockstep stream replays THIS fused step (its token output
            # is replicated so every process reads locally) — the cliff
            # is dead on every mesh (ISSUE 12 legs 3-4).
            first = self.counters.note_dispatch("decode1g", bucket,
                                                work.pages)
            gfn = self._greedy_step_fn()
            gargs = (self.params, self.cache, self._dev(tokens),
                     self._dev(positions), self._dev(seq_lens),
                     self._dev(bts), zeros) + state
            self._harvest_program(first, "decode1g",
                                  (bucket, work.pages), gfn, gargs)
            res = gfn(*gargs)
            if self._moe:
                toks_dev, self.cache, load = res
                aux = self._moe_report(load)
                self._note_moe_dev(aux["load"], aux.get("touched"),
                                   self.config.model.num_moe_layers,
                                   bucket, decode=True)
                if self.block_record is not None and "routing" in aux:
                    self._record_decode(live, rows, aux["routing"][None])
            else:
                toks_dev, self.cache = res
            self.counters.host_syncs += 1
            enter(PHASE_WAIT_DEVICE)
            sampled = np.asarray(jax.device_get(toks_dev))[np.asarray(rows)]
            enter(PHASE_EMIT)
            lps = None
        else:
            first = self.counters.note_dispatch("decode1", bucket,
                                                work.pages)
            tok_d = self._dev(tokens)
            pos_d = self._dev(positions)
            sl_d = self._dev(seq_lens)
            bts_d = self._dev(bts)
            self._harvest_program(
                first, "decode1", (bucket, work.pages), self._step,
                (self.params, self.cache, tok_d, pos_d, sl_d, bts_d,
                 zeros) + state)
            logits, self.cache = self._run_step(
                tok_d, pos_d, sl_d, bts_d, zeros, state=state)
            sampled, lps = self._sample_rows(
                self._select_rows(logits, rows), live)
        deltas = []
        for i, req in enumerate(live):
            # Publish blocks sealed by *previous* tokens before appending:
            # if this token finishes the request, its state is dropped and a
            # late publish would re-emit the whole sequence from scratch.
            self._publish_completed_blocks(req)
            deltas.append(self._append_token(
                req, int(sampled[i]),
                float(lps[i]) if lps is not None else None))
        return deltas

    @property
    def _fused_greedy_capable(self) -> bool:
        """Engines whose all-greedy single-step decode runs the fused
        forward+argmax program.  Reads the capability table (ISSUE 12):
        meshless (raw forward captured), every single-process mesh
        (make_sharded_greedy_step), pp (the all-in-one stage program,
        make_pp_greedy_step), and multihost — the fused step replicates
        its token output so every lockstep process reads it locally."""
        if self._fwd_raw is not None:
            return True
        return self.mesh is not None and plane_capability(
            self.mesh,
            PlaneSpec(fused=True, quant=self.cache_cfg.quantized,
                      dp_attention=self.config.dp_attention,
                      dp_local=self._dp_local),
            multihost=self._mh).ok

    @engine_thread_only
    @hot_path
    def _greedy_step_fn(self):
        """Lazily-jitted fused greedy single step: the forward and the
        argmax compile into one program, so the non-window decode path
        costs one dispatch and returns [B] tokens instead of [B, V]
        logits.  Sharded non-pp engines build it through the unified
        make_sharded_step builder (plane.fused=True) with the engine's
        own sharding choices; pp engines through the all-in-one stage
        program (pipeline.make_pp_greedy_step) — so every mesh sheds
        the single-step cliff exactly like meshless ones."""
        if self._greedy_fused is None:
            if self._pp:
                from dynamo_tpu.parallel.pipeline import make_pp_greedy_step

                self._greedy_fused = make_pp_greedy_step(
                    self.config.model, self.block_size, self.mesh,
                    self.config.pp_microbatches,
                    kv_quant=self.cache_cfg.quantized)
                return self._greedy_fused
            if self.mesh is not None:
                from dynamo_tpu.parallel.sharding import (
                    make_sharded_greedy_step)

                self._greedy_fused = make_sharded_greedy_step(
                    self.config.model, self.block_size, self.mesh,
                    moe_mode=getattr(self, "_moe_mode", "auto"),
                    with_expert_load=self._moe,
                    dp_attention=self.config.dp_attention,
                    use_pallas_decode=self._use_pallas,
                    dp_local=self._dp_local,
                    kv_quant=self.cache_cfg.quantized)
                return self._greedy_fused
            fwd = self._fwd_raw
            moe = self._moe

            def fused(params, cache, tokens, positions, seq_lens, bts,
                      sample_pos, *state):
                # `state`: the rows' state slots, for a model with
                # state-space layers; nothing for any other.
                out = fwd(params, cache, tokens, positions, seq_lens,
                          bts, sample_pos, *state)
                if moe:
                    logits, cache, load = out
                    return (jnp.argmax(logits, -1).astype(jnp.int32),
                            cache, load)
                logits, cache = out
                return jnp.argmax(logits, -1).astype(jnp.int32), cache

            self._greedy_fused = self._stored(
                jax.jit(fused, donate_argnums=(1,)), "greedy_step")
        return self._greedy_fused

    # -- pipelined decode windows ------------------------------------------

    @engine_thread_only
    @hot_path
    def _window_fn(self, greedy_only: bool):
        fn = self._window_fns.get(greedy_only)
        if fn is None:
            if self._pp:
                # pp window (ISSUE 12 leg 3): K schedule passes in one
                # dispatch with on-device token feedback, so pp decode
                # rides the same pipelined window path as every mesh.
                from dynamo_tpu.parallel.pipeline import (
                    make_pp_decode_window)

                fn = make_pp_decode_window(
                    self.config.model, self.block_size, self.mesh,
                    self.config.pp_microbatches,
                    self.config.decode_window,
                    greedy_only=greedy_only,
                    kv_quant=self.cache_cfg.quantized)
            elif self.mesh is not None:
                from dynamo_tpu.parallel.sharding import make_sharded_window

                fn = make_sharded_window(
                    self.config.model, self.block_size, self.mesh,
                    self.config.decode_window,
                    greedy_only=greedy_only,
                    use_pallas_decode=self._use_pallas,
                    dp_attention=self.config.dp_attention,
                    dp_local=self._dp_local,
                    kv_quant=self.cache_cfg.quantized,
                    moe_mode=getattr(self, "_moe_mode", "auto"))
            else:
                from dynamo_tpu.models.llama import make_decode_window

                fn = self._stored(
                    jax.jit(
                        self._window_tables_last(make_decode_window(
                            self.config.model, self.block_size,
                            self.config.decode_window,
                            use_pallas_decode=self._use_pallas,
                            greedy_only=greedy_only,
                            moe_mode=getattr(self, "_moe_mode", "dense"),
                            with_expert_load=self._moe,
                            moe_aux=self._moe)),
                        donate_argnums=(1,)),
                    "window", greedy_only=greedy_only)
            self._window_fns[greedy_only] = fn
        return fn

    @hot_path
    def _dispatch_window(self, work: DecodeWork) -> Optional[List[TokenDelta]]:
        """Dispatch one fused K-token decode window (no host sync); sync
        and emit the window from pipeline_depth dispatches ago.  Returns
        None if page capacity can't cover the lookahead (caller drains and
        falls back to the single-step path).

        Steady state is ZERO host→device uploads: the window function
        returns advanced positions/seq_lens/offsets as device arrays, and
        the per-row sampling arrays are reuploaded only when the request
        set (or a row's sampling/pages) changes."""
        self.counters.enter(PHASE_DISPATCH_WINDOW)
        K = self.config.decode_window
        reqs = work.requests
        bucket = (self._dp_rows if self._dp_local
                  else self._pad_rows(work.bucket))
        rows = [self._decode_row(r, i) for i, r in enumerate(reqs)]
        lag = len(self._inflight)  # windows dispatched but unsynced

        # Shadow context: host bookkeeping lags the device by lag*K tokens.
        shadows = []
        for req in reqs:
            shadow = req.context_len + lag * K
            if not self.scheduler.ensure_capacity(req, shadow + K):
                return None
            shadows.append(shadow)

        bs = self.block_size
        width = self.scheduler.config.bucket_for_pages(
            max((s + K + bs - 1) // bs for s in shadows))
        greedy_only = all(r.sampling.temperature <= 0 for r in reqs)
        sig = (tuple(r.request_id for r in reqs), bucket, width, greedy_only,
               tuple((r.sampling.temperature, r.sampling.top_k,
                      r.sampling.top_p, r.sampling.seed) for r in reqs))
        want_pos = np.asarray([s - 1 for s in shadows], np.int32)
        st = self._window_state
        if (st is None or st["sig"] != sig
                or not np.array_equal(st["pos_host"][rows], want_pos)):
            st = self._build_window_state(reqs, rows, bucket, width,
                                          shadows, lag, K, greedy_only,
                                          sig)
            self.counters.h2d_uploads += 1
        pages_sig = self._pages_sig(reqs)
        if st["pages_sig"] != pages_sig:
            bts = np.zeros((bucket, width), np.int32)
            for i, req in zip(rows, reqs):
                n = min(len(req.pages), width)
                bts[i, :n] = req.pages[:n]
            st["bts"] = self._dev_row2(bts)
            if self._window:
                st["window_bts"] = self._dev_row2(
                    self._window_rows(bucket, width, reqs, rows))
            st["pages_sig"] = pages_sig
            self.counters.h2d_uploads += 1
        self._window_state = st
        self.counters.window_dispatches += 1
        first = self.counters.note_dispatch("window", greedy_only, bucket,
                                            width)
        fl = self.flight
        if fl.enabled:
            # THE per-window ring write (budget: one per window
            # dispatch; tests/test_flight_recorder.py::
            # test_steady_window_recorder_on_is_byte_identical).
            fl.record("window", bucket=bucket, width=width, lag=lag)
        # Effective-bytes model, bytes half: window step i of K reads
        # context shadow+i per row.  The TOKEN half is tallied at sync
        # time from what actually reaches the output stream — counting
        # K*rows here would credit the discarded tails of finished
        # requests and overshoot windows, understating bytes/token
        # (the spec path makes the same appended-only choice).
        self.counters.note_kv_read(
            sum(s * K + K * (K - 1) // 2 for s in shadows)
            * self._ctx_token_bytes_chip
            + self._window_read_bytes((s, K) for s in shadows), 0)

        if lag:
            last_tokens = self._inflight[-1]["out"][K - 1]  # device, no sync
        else:
            toks = np.zeros((bucket,), np.int32)
            for i, req in zip(rows, reqs):
                toks[i] = (req.output_tokens[-1] if req.output_tokens
                           else req.prompt_tokens[-1])
            last_tokens = self._dev_row(toks)

        moe_read = None
        wfn = self._window_fn(greedy_only)
        wargs = (self.params, self.cache, last_tokens,
                 st["pos"], st["seq"], st["bts"], st["temp"], st["topk"],
                 st["topp"], st["keys"], st["off"])
        if self._ssm:
            wargs += (st["slots"],)
            self.counters.note_ssm_decode(len(reqs), K, bucket)
        if self._window:
            wargs += (st["window_bts"],)
            self.counters.note_attn_pairs(
                "decode", [(s - 1, K) for s in shadows], calls=K)
        self._harvest_program(first, "window",
                              (greedy_only, bucket, width), wfn, wargs)
        res = wfn(*wargs)
        if self._moe:
            (self.cache, out, st["pos"], st["seq"], st["off"],
             load) = res
            # Device-side accumulation; snapshot_expert_load syncs on
            # the metrics cadence (same discipline as _run_step).
            aux = self._moe_report(load)
            self._note_moe_dev(aux["load"], aux.get("touched"),
                               self.config.model.num_moe_layers * K,
                               bucket, decode=True)
            if self.block_record is not None and "routing" in aux:
                self._record_decode(reqs, rows, aux["routing"],
                                    first=[s - 1 for s in shadows])
            if "touched" in aux:
                # What the expert layers reported since the last window
                # (this one's, and the prefill chunks' and single steps'
                # before it) rides this window's one read: no sync of its
                # own, as the block path's report rides the block's read.
                moe_read = self._take_moe_dev()
        else:
            (self.cache, out, st["pos"], st["seq"], st["off"]) = res
        st["pos_host"][rows] += K
        # Start the device→host copy NOW: copy_to_host_async enqueues the
        # transfer without stalling the execution stream, and the fetch
        # thread's np.asarray then finds the bytes already on their way.
        try:
            out.copy_to_host_async()
        except Exception:
            # Backend without async host copies: fetch still works, the
            # overlap optimisation just silently degrades — say so ONCE
            # (this fires per window; unbounded logging would flood).
            if not self._async_copy_warned:
                self._async_copy_warned = True
                logger.warning(
                    "backend lacks copy_to_host_async; window token "
                    "fetches will pay a blocking device->host copy")
        self._inflight.append({
            "rids": [r.request_id for r in reqs],
            "reqs": list(reqs),
            "rows": rows,
            "out": out,
            # The chunk that rode since the previous window sits on the
            # device queue BEFORE this window, so this window's sync
            # interval absorbs its execution time (note_window_interval).
            "chunk": self._chunk_key,
            "fetch": (self._fetch_pool.submit(np.asarray, out)
                      if moe_read is None else self._fetch_pool.submit(
                          jax.device_get, (out,) + moe_read[0])),
            "moe_layers": None if moe_read is None else moe_read[1],
        })
        self._chunk_key = 0
        if len(self._inflight) > self.config.window_pipeline_depth:
            return self._sync_one_window()
        return []

    def _pages_sig(self, reqs) -> tuple:
        """What a window's uploaded tables were built from: the pages each
        row holds, and of a model with window layers the window-group blocks
        it holds and has let go."""
        if not self._window:
            return tuple(len(r.pages) for r in reqs)
        return tuple((len(r.pages), len(r.window_pages),
                      r.window_pages.count(0)) for r in reqs)

    def _build_window_state(self, reqs, rows, bucket, width, shadows,
                            lag, K, greedy_only, sig) -> Dict:
        """Upload the per-row window arrays (one-time per request-set
        change; the window advances them on device afterwards).  `rows`
        maps request order to device rows (slot-pinned under dp-attention
        locality).  The window dispatched with them is the first decode
        dispatch that holds a merged row: the request-state clock marks
        `decode` here, where the cohort's row set changes, and not per
        row per dispatch."""
        positions0 = np.full((bucket,), self._pad_position, np.int32)
        seq_lens0 = np.zeros((bucket,), np.int32)
        bts = np.zeros((bucket, width), np.int32)
        temp = np.zeros((bucket,), np.float32)
        top_k = np.zeros((bucket,), np.int32)
        top_p = np.ones((bucket,), np.float32)
        offsets = np.zeros((bucket,), np.int32)
        self._mark_decode(reqs, joins=True)
        for j, (i, req) in enumerate(zip(rows, reqs)):
            positions0[i] = shadows[j] - 1
            seq_lens0[i] = shadows[j]
            n = min(len(req.pages), width)
            bts[i, :n] = req.pages[:n]
            temp[i] = req.sampling.temperature
            top_k[i] = req.sampling.top_k
            top_p[i] = req.sampling.top_p
            offsets[i] = (req.sampling.seed_offset + req.prior_output
                          + len(req.output_tokens) + lag * K)
        # Keys are RAW uint32 key data (wrapped on device by the window
        # fn): host-buildable numpy, which the multihost global-array
        # conversion requires (typed key arrays can't cross it).
        if greedy_only:
            key_data = np.zeros((bucket, 2), np.uint32)  # unused by argmax
        else:
            # One base key per request-set build; per-token randomness
            # comes from fold_in(base, offset) with offsets advancing on
            # device, so seeded streams stay reproducible and unseeded
            # rows never repeat a key.
            self._rng, sub = jax.random.split(self._rng)
            key_data = np.array(jax.random.key_data(
                jax.random.split(sub, bucket)))  # copy: jax views are RO
            for i, req in zip(rows, reqs):
                if req.sampling.seed is not None:
                    key_data[i] = np.asarray(jax.random.key_data(
                        jax.random.key(req.sampling.seed)))
        pos_host = positions0.copy()
        state = {}
        if self._ssm:
            state["slots"] = self._dev_row(
                self._slot_rows(bucket, reqs, rows))
        if self._window:
            state["window_bts"] = self._dev_row2(
                self._window_rows(bucket, width, reqs, rows))
        return {
            **state,
            "sig": sig,
            "pages_sig": self._pages_sig(reqs),
            "pos_host": pos_host,
            "pos": self._dev_row(positions0),
            "seq": self._dev_row(seq_lens0),
            "bts": self._dev_row2(bts),
            "temp": self._dev_row(temp),
            "topk": self._dev_row(top_k),
            "topp": self._dev_row(top_p),
            "keys": self._dev_row2(key_data),
            "off": self._dev_row(offsets),
        }

    @hot_path
    def _sync_one_window(self) -> List[TokenDelta]:
        entry = self._inflight.pop(0)
        self.counters.host_syncs += 1
        self.counters.window_syncs += 1
        self.counters.enter(PHASE_WAIT_DEVICE)
        # dynamo-lint: disable=DL001 THE one counted sync per window
        tokens = entry["fetch"].result()                   # [K, bucket]
        if entry.get("moe_layers") is not None:
            tokens, load, touched, dec, cap = tokens  # host arrays already
            layers, dec_layers, packed, cap_layers = entry["moe_layers"]
            self._fold_moe_stats(load, touched, layers, (dec, dec_layers),
                                 packed, (cap, cap_layers))
        self.counters.enter(PHASE_EMIT)
        # In a full pipeline the wall interval between consecutive syncs
        # tracks device window time; windows with a chunk behind them
        # carry the chunk's cost as excess.  Host clock only.
        now = self._clock()
        if self._last_window_sync_ts is not None:
            self.counters.note_window_interval(
                now - self._last_window_sync_ts, entry["chunk"])
        # A draining pipeline's next interval is fill-distorted; only
        # back-to-back syncs with work still in flight are samples.
        self._last_window_sync_ts = now if self._inflight else None
        deltas: List[TokenDelta] = []
        for i in range(tokens.shape[0]):
            for col, req in zip(entry["rows"], entry["reqs"]):
                if (req.request_id not in self._requests
                        or req.state is not RequestState.DECODE):
                    continue  # finished/cancelled mid-window: discard tail
                self._publish_completed_blocks(req)
                deltas.append(self._append_token(req, int(tokens[i, col])))
                self.counters.note_kv_read(0, 1)  # real emission only
        return deltas

    def _drain_inflight(self, deltas: List[TokenDelta],
                        deliver=None) -> None:
        """Read every window in flight, oldest first, into `deltas`.  With
        a serving loop attached (`deliver`, from `step()`) what the
        iteration holds so far goes to it after each window, as it is
        read, and not at the iteration's end: a full pipeline takes most
        of a second to read, and a stream whose last token is in its first
        window would end that much later for its client."""
        while self._inflight:
            deltas.extend(self._sync_one_window())
            if deliver is not None and deltas:
                self.counters.enter(PHASE_DELIVER)
                deliver(deltas)
                del deltas[:]

    def _mark_decode(self, reqs, joins: bool = False) -> None:
        """The request-state clock's `decode`: `reqs` are the rows of a
        decode dispatch about to go out (a window over a changed cohort,
        a single or speculative step, a block call); those it is the
        first to hold enter the state, all at one clock reading.  `joins`
        (a window's cohort) also counts each such row in `cohort_joins`:
        at the `chunk` where no decode dispatch went out between the
        chunk that completed its prompt and this one, else at the
        `settle` (rows batched in the ready pool, a window or single step
        of the old cohort dispatched in between)."""
        c = self.counters
        now = 0
        for req in reqs:
            if req.clock_state != RS_DECODE:
                if joins:
                    c.cohort_joins[
                        JOIN_AT_CHUNK if req.decode_dispatches_at_prefill
                        == c.decode_dispatches else JOIN_AT_SETTLE] += 1
                now = c.request_state(req, RS_DECODE, now)

    def _preempt_or_finish(self, req: Request) -> None:
        """KV blocks exhausted mid-decode.  Preempt-and-recompute when other
        requests hold pages (they will free some); a lone request that OOMs
        would just thrash, so it finishes with LENGTH (the reference engines'
        preemption semantics, vLLM-style recompute)."""
        total_need = self.scheduler._pages_needed(req.total_len + 1)
        if (len(self.scheduler.running) <= 1
                or total_need > self.allocator.num_blocks - 1):
            self._finish(req, FinishReason.LENGTH)
            return
        logger.info("preempting %s: out of KV blocks", req.request_id)
        fl = self.flight
        if fl.enabled:
            fl.record("preempt", rid=req.request_id, need_pages=total_need)
        if not self._managed_cache:
            # Plain allocator: the pages really are gone; re-publish on the
            # recompute pass.  (Managed source keeps sealed blocks resident
            # as inactive entries — its eviction hook reports removals.)
            self._publish_removed_blocks(req)
        # Reset seal tracking either way: publication must follow the
        # *recomputed* KV, never the pre-preemption block list (a stale list
        # would register pages whose KV hasn't been rewritten yet).
        self._hash_seqs.pop(req.request_id, None)
        self._published_blocks.pop(req.request_id, None)
        self.scheduler.preempt(req)

    def _qos_preempt(self, req: Request) -> None:
        """Scheduler-chosen QoS victim (best-effort request displaced by a
        higher class or by SLO burn): recompute-preempt it, then demote
        its sealed blocks G1→host so the freed HBM is real capacity and
        the eventual resume onboards KV from the tier instead of paying a
        full re-prefill.  Mirrors _preempt_or_finish's seal-bookkeeping
        reset (publication must follow recomputed KV)."""
        rid = req.request_id
        seq = self._hash_seqs.get(rid)
        published = self._published_blocks.get(rid, 0)
        sealed = ([b.block_hash for b in seq.blocks[:published]]
                  if seq is not None else [])
        n_sealed = len(sealed)
        if not self._managed_cache:
            self._publish_removed_blocks(req)
        self._hash_seqs.pop(rid, None)
        self._published_blocks.pop(rid, None)
        self.scheduler.preempt(req)
        demoted = 0
        if self._managed_cache and sealed:
            demoted = self.allocator.manager.demote_blocks(sealed)
            self.qos_demoted_blocks += demoted
        fl = self.flight
        if fl.enabled:
            fl.record("qos_preempt", rid=rid, prio=req.priority,
                      sealed=n_sealed, demoted=demoted)
        logger.info("qos-preempted %s (priority %d): %d sealed blocks, "
                    "%d demoted to host tier", rid, req.priority,
                    n_sealed, demoted)

    def _fetch_host(self, arr) -> np.ndarray:
        """Device → host read valid under any topology (multihost
        allgathers non-replicated arrays; every process reaches this
        point in lockstep)."""
        if self._mh:
            from dynamo_tpu.parallel.multihost import fetch

            return fetch(arr)
        return np.asarray(arr)

    def _select_rows(self, logits: jax.Array, rows: List[int]) -> jax.Array:
        """Row-gather of the logits the sampler needs.  Multihost: pull
        the (replicated) logits to host and re-enter as a process-LOCAL
        array, so the whole sampling path below runs identically-local on
        every process (no cross-process eager ops, no reverse channel —
        followers derive the same tokens from the same bytes)."""
        if self._mh:
            return jnp.asarray(self._fetch_host(logits)[np.asarray(rows)])
        return logits[jnp.asarray(rows)]

    def _sample_rows(self, logits: jax.Array, reqs: List[Request],
                     async_fetch: bool = False):
        """Returns (tokens[n], logprobs[n] or None) — logprobs computed on
        device (one extra fetch) only when some request asked.

        `async_fetch`: all device work dispatches now (engine thread);
        the host fetch rides the pool thread and a Future of the same
        tuple is returned instead."""
        n = logits.shape[0]
        reqs = reqs[:n]
        want_lp = any(r.sampling.logprobs for r in reqs)
        self.counters.note_dispatch(
            "sample", n, all(r.sampling.temperature <= 0 for r in reqs),
            want_lp)

        if all(r.sampling.temperature <= 0 for r in reqs):
            # Greedy fast path: no keys, no sort — a plain argmax (the
            # common serving mix; per-row key plumbing here cost dozens of
            # device round-trips per step in r1).
            tokens_dev = greedy_sample(logits)
        else:
            temp = np.asarray([r.sampling.temperature for r in reqs]
                              + [0.0] * (n - len(reqs)), np.float32)
            top_k = np.asarray([r.sampling.top_k for r in reqs]
                               + [0] * (n - len(reqs)), np.int32)
            top_p = np.asarray([r.sampling.top_p for r in reqs]
                               + [1.0] * (n - len(reqs)), np.float32)
            # One split yields the whole batch's fresh keys (a single
            # device op); seeded rows overwrite theirs so a seeded
            # stream depends only on (seed, token index) — reproducible
            # across batch mixes and preemption (prior_output keeps the
            # index monotonic).  Shared with the spec path (_row_keys).
            tokens_dev = sample(logits, jnp.asarray(temp),
                                jnp.asarray(top_k), jnp.asarray(top_p),
                                self._row_keys(reqs, n))
        lp_dev = chosen_logprobs(logits, tokens_dev) if want_lp else None

        def fetch():
            if lp_dev is None:
                return np.asarray(jax.device_get(tokens_dev)), None
            toks, lps = jax.device_get((tokens_dev, lp_dev))
            return np.asarray(toks), np.asarray(lps)

        if async_fetch:
            return self._fetch_pool.submit(fetch)
        self.counters.host_syncs += 1
        self.counters.enter(PHASE_WAIT_DEVICE)
        out = fetch()
        self.counters.enter(PHASE_EMIT)
        return out

    @hot_path
    def _append_token(self, req: Request, token: int,
                      logprob: Optional[float] = None) -> TokenDelta:
        c = self.counters
        timings = None
        if not req.first_token_ns:
            # A causal request's first token is sampled from its prefill,
            # before any decode dispatch holds its row; a block-diffusion
            # request (and one preempted before its first token) is past
            # `first_token` by now and stays where it is.
            req.first_token_ns = (
                c.request_state(req, RS_COHORT_WAIT)
                if req.clock_state == RS_FIRST_TOKEN
                else time.perf_counter_ns())
            c.request_first_tokens += 1
            timings = self._first_token_timings(req)
        c.request_output_tokens += 1
        req.output_tokens.append(token)
        lp = ([logprob] if (logprob is not None and req.sampling.logprobs)
              else None)
        stop = token in req.sampling.stop_token_ids
        length = (req.prior_output + len(req.output_tokens)
                  >= req.sampling.max_tokens)
        if stop or length:
            self._finish(req, FinishReason.STOP if stop else FinishReason.LENGTH)
            if request_ledger.enabled():
                # `_finish` took the request off the clock, so its seconds
                # in every state are closed.
                timings = dict(
                    timings or (),
                    cohort_wait_s=req.state_ns[RS_COHORT_WAIT] / 1e9,
                    preempted_s=req.state_ns[RS_PREEMPTED] / 1e9)
            delta = TokenDelta(req.request_id, [token], finished=True,
                               finish_reason=req.finish_reason, logprobs=lp,
                               timings=timings)
            self._drop(req)
            return delta
        return TokenDelta(req.request_id, [token], logprobs=lp,
                          timings=timings)

    def _first_token_timings(self, req: Request) -> Optional[dict]:
        """The engine's share of a request's TTFT, at the moment its first
        token lands, from the request-state clock's stamps: THE one place
        the tracer's `engine.queue_wait` / `engine.prefill` / `engine.ttft`
        spans and the ledger's `queue` / `budget_wait` / `prefill` /
        `first_token` stamps are derived.  Durations are differences within
        `req.state_entry_ns` (`perf_counter_ns`); the tracer and the ledger
        place instants on `time.monotonic()`, so one paired reading of both
        clocks converts them, assuming no shared origin.  A state the
        request never entered (it was preempted on the way) ends where the
        next one it did enter begins.  Pure host bookkeeping, and nothing
        at all unless the ledger is on or tracing is enabled AND the
        serving layer bound a context for this request id
        (LocalEngineClient / engine_wire_handler).  Returns what rides the
        first token's delta to the ledger (None with the ledger off)."""
        from dynamo_tpu.runtime import tracing

        tracer = tracing.get_tracer()
        ctx = tracer.ctx_for(req.request_id) if tracer.enabled else None
        ledger_on = request_ledger.enabled()
        if ctx is None and not ledger_on:
            return None
        mono, ns = time.monotonic(), time.perf_counter_ns()
        entry = req.state_entry_ns
        instants = []
        t = req.first_token_ns
        for state in (RS_FIRST_TOKEN, RS_PREFILL, RS_BUDGET_WAIT,
                      RS_WAITING):
            if entry[state]:
                t = min(t, entry[state])
            instants.append(mono - (ns - t) / 1e9)
        pf_end, pf_start, admitted, arrival = instants
        first = mono - (ns - req.first_token_ns) / 1e9
        if ctx is not None:
            rid = req.request_id
            tracer.record_span(
                "engine.queue_wait", ctx, arrival, pf_start,
                attrs={"request_id": rid,
                       "budget_wait_s": round(pf_start - admitted, 6)})
            tracer.record_span(
                "engine.prefill", ctx, pf_start, pf_end,
                attrs={"request_id": rid,
                       "prompt_tokens": len(req.prompt_tokens)})
            tracer.record_span("engine.ttft", ctx, arrival, first,
                               attrs={"request_id": rid})
        if not ledger_on:
            return None
        return {"arrival": arrival, "admitted": admitted,
                "prefill_start": pf_start, "prefill_end": pf_end,
                "first_token": first,
                "prompt_tokens": len(req.prompt_tokens),
                "cached_tokens": req.cached_prompt_tokens,
                "preempts": req.preempts}

    def _finish(self, req: Request, reason: FinishReason) -> None:
        # With the managed source, sealed blocks stay resident (inactive,
        # matchable) after finish — REMOVED comes from its eviction hook.
        if not self._managed_cache:
            self._publish_removed_blocks(req)
        self.scheduler.finish(req, reason)

    def _drop(self, req: Request) -> None:
        self._requests.pop(req.request_id, None)
        self._hash_seqs.pop(req.request_id, None)
        self._published_blocks.pop(req.request_id, None)

    @engine_thread_only
    def clear_prefix_cache(self) -> int:
        """Admin flush of all reusable cached blocks (reference
        `clear_kv_blocks.rs`); returns the number dropped.  Must run on
        the engine thread."""
        self.drain_block_call()
        if self._lockstep is not None:
            self._lockstep.broadcast({"op": "clear"})
        clear = getattr(self.allocator, "clear_cache", None)
        return clear() if clear is not None else 0

    # -- embeddings --------------------------------------------------------

    @engine_thread_only
    def embed_tokens(self, token_lists: List[List[int]]) -> np.ndarray:
        """Last-token hidden-state embeddings for each prompt: [n, H] f32.

        Runs one prefill per prompt (padded to the prefill bucket) with
        temporarily-allocated pages that are released afterward — the
        /v1/embeddings surface (reference `http/service/openai.rs:315`).
        Must run on the engine thread (InferenceEngine wraps it)."""
        # Declared-impossible combos (pp / multihost) raise the
        # capability table's pointed error — one source of truth.
        check_plane(self.mesh, PlaneSpec(role="embed"),
                    multihost=self._mh)
        if self._window:
            raise ValueError("embeddings are not wired for a model with "
                             "window layers (its scratch prompts would need "
                             "window-group pages of their own)")
        self.drain_block_call()
        if self._embed_step is None:
            if self.mesh is not None:
                from dynamo_tpu.parallel.sharding import (
                    make_sharded_embed_step)

                self._embed_step = make_sharded_embed_step(
                    self.config.model, self.block_size, self.mesh,
                    dp_attention=self.config.dp_attention,
                    dp_local=self._dp_local,
                    kv_quant=self.cache_cfg.quantized)
            else:
                from dynamo_tpu.models.llama import make_forward_step as mfs

                self._embed_step = jax.jit(
                    mfs(self.config.model, self.block_size,
                        use_pallas_decode=False, return_hidden=True),
                    donate_argnums=(1,))
        sched = self.scheduler.config
        for toks in token_lists:
            if len(toks) == 0:
                raise ValueError("empty embedding input")
            if len(toks) > sched.max_prefill_chunk:
                raise ValueError(
                    f"embedding input of {len(toks)} tokens exceeds the "
                    f"prefill chunk ceiling {sched.max_prefill_chunk}")
        out = np.zeros((len(token_lists), self.config.model.hidden_size),
                       np.float32)
        # Pack up to R prompts per device call — under a sharded mesh the
        # row count must be a multiple of the batch divisor anyway, so
        # fill those rows with real prompts instead of zero padding.
        R = max(self._pad_rows(1), 1)
        for start in range(0, len(token_lists), R):
            group = token_lists[start: start + R]
            T = sched.bucket_for_prefill(max(len(t) for t in group))
            per_pages = [(len(t) + self.block_size - 1) // self.block_size
                         for t in group]
            width = sched.bucket_for_pages(max(per_pages))
            # Allocate inside the guarded region: a partial-failure midway
            # through the group must release what was already taken.
            pages: List[List[int]] = []
            try:
                for n in per_pages:
                    pages.append(self.allocator.allocate(n))
                tokens = np.zeros((R, T), np.int32)
                positions = np.full((R, T), self._pad_position, np.int32)
                bt = np.zeros((R, width), np.int32)
                seq_lens = np.zeros((R,), np.int32)
                sample = np.zeros((R,), np.int32)
                for i, toks in enumerate(group):
                    L = len(toks)
                    tokens[i, :L] = toks
                    positions[i, :L] = np.arange(L)
                    bt[i, : per_pages[i]] = pages[i]
                    seq_lens[i] = L
                    sample[i] = L - 1
                # Embedding prompts own no state slot: each starts from
                # zero (position 0) and leaves its state on the scratch one.
                state = ({"state_slots": jnp.asarray(self._slot_rows(R))}
                         if self._ssm else {})
                hidden, self.cache = self._embed_step(
                    self.params, self.cache,
                    jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(seq_lens), jnp.asarray(bt),
                    jnp.asarray(sample), **state)
                out[start: start + len(group)] = np.asarray(
                    jax.device_get(hidden[: len(group)]))
            finally:
                for p in pages:
                    self.allocator.release(p)
        return out

    # -- cross-worker KV transfer ------------------------------------------

    def _refuse_transfer(self) -> None:
        """A model that keeps more of a sequence than its full-group blocks
        does not move one by them."""
        if self._ssm:
            raise ValueError(STATE_NO_TRANSFER)
        if self._window:
            from dynamo_tpu.models.config import WINDOW_NO_TRANSFER

            raise ValueError(WINDOW_NO_TRANSFER)

    @engine_thread_only
    def export_blocks(self, hashes) -> Dict[int, np.ndarray]:
        """Raw KV bytes for every requested block resident in any tier
        (the extract side of the worker↔worker data plane).  Must run on
        the engine thread — InferenceEngine wraps it as a command."""
        out: Dict[int, np.ndarray] = {}
        self._refuse_transfer()
        if not self._managed_cache:
            return out
        self.drain_block_call()
        if self._lockstep is not None:
            # Followers must join the extract collectives (sharded cache).
            self._lockstep.broadcast({"op": "export",
                                      "hashes": [int(h) for h in hashes]})
        for h in hashes:
            data = self.allocator.manager.export_block(h)
            if data is not None:
                out[h] = data
        return out

    @engine_thread_only
    def export_blocks_device(self, hashes, canonical: bool = True
                             ) -> Dict[int, object]:
        """G1-resident blocks as DEVICE arrays (the device-direct transfer
        plane's extract side; no host staging).  Engine thread only.

        Sharded caches (tp/dp/sp mesh), `canonical=True`: the extracted
        block gathers onto device 0 over ICI — the pjrt transport moves
        single-device buffers, and the canonical [2, L, bs, F] block
        format is sharding-independent, so a prefill tp=x → decode tp=y
        handoff is a gather here + scatter at the peer's inject (the
        XLA-collective answer to the reference's `block_copy.cu:41`
        layout transpose; `disagg_serving.md:96-99`).

        `canonical=False` (ISSUE 16, the local device fabric): skip the
        gather and hand the block out in whatever sharding the extract
        produced — the puller's ONE device_put reshards source layout →
        dest layout directly (arbitrary PartitionSpec pairs), and no
        device ever holds the whole block."""
        out: Dict[int, object] = {}
        self._refuse_transfer()
        if not self._managed_cache:
            return out
        self.drain_block_call()
        single = None
        if self.mesh is not None and canonical:
            from jax.sharding import SingleDeviceSharding

            single = SingleDeviceSharding(jax.devices()[0])
        for h in hashes:
            data = self.allocator.manager.export_block_device(h)
            if data is not None:
                if single is not None:
                    data = jax.device_put(data, single)
                out[h] = data
        return out

    @property
    def block_inject_sharding(self):
        """The sharding `_inject_block` consumes wire blocks at — what
        the device-transfer plane should land pulled arrays ON so the
        inject's own device_put is a no-op instead of a second copy
        (pre-fix every pull committed to jax.devices()[0], which under a
        mesh double-copied on inject and piled every block onto one
        chip).  Meshless: the cache's own device (host metadata read —
        safe off-thread).  Single-process mesh: the wire block sharded
        the way the CACHE shards (kv_cache.wire_block_pspec) — the
        generalized cross-mesh landing, so a pull from ANY source layout
        reshards straight into this engine's layout with no replication
        hop.  pp / multihost meshes keep the replicated layout their
        dedicated block ops scatter from."""
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            if self._mh or self._pp:
                return NamedSharding(self.mesh, PartitionSpec())
            sh = self.__dict__.get("_wire_inject_sharding")
            if sh is None:
                from dynamo_tpu.parallel.sharding import cache_pspecs

                spec = kvc.wire_block_pspec(
                    self.mesh,
                    cache_pspecs(self.config.model.num_layers,
                                 dp_attention=self.config.dp_attention,
                                 dp_local=self._dp_local,
                                 kv_quant=self.cache_cfg.quantized),
                    self.cache_cfg.block_wire_shape)
                sh = NamedSharding(self.mesh, spec)
                self.__dict__["_wire_inject_sharding"] = sh
            return sh
        leaves = jax.tree.leaves(self.cache)
        if leaves:
            return leaves[0].sharding
        return jax.sharding.SingleDeviceSharding(jax.devices()[0])

    @engine_thread_only
    def resident_prefix_blocks(self, hashes) -> int:
        """Length of the contiguous prefix of `hashes` already resident
        in ANY local tier (G1/G2/G3) — host-dict lookups only, no device
        work.  The fleet prefix-share pull consults this so blocks a
        repeat request (or an earlier pull) already landed are never
        re-fetched over the wire."""
        if not self._managed_cache:
            return 0
        mgr = self.allocator.manager
        n = 0
        for h in hashes:
            if (mgr.device.registry.lookup(h) is not None
                    or (mgr.host is not None
                        and mgr.host.registry.lookup(h) is not None)
                    or (mgr.disk is not None
                        and mgr.disk.registry.lookup(h) is not None)):
                n += 1
            else:
                break
        return n

    @engine_thread_only
    def import_blocks(self, blocks: Dict[int, np.ndarray]) -> int:
        """Inject fetched blocks into G1 as registered prefix-cache entries;
        a subsequent add_request with the matching prompt prefix skips
        their prefill (the decode-side onboard of disaggregated P/D)."""
        self._refuse_transfer()
        if not self._managed_cache:
            return 0
        self.drain_block_call()
        if self._lockstep is not None:
            from dynamo_tpu.parallel.multihost import encode_blocks

            self._lockstep.broadcast({"op": "import",
                                      "blocks": encode_blocks(blocks)})
        n = 0
        for h, data in blocks.items():
            if self.allocator.manager.import_block(h, data):
                n += 1
        return n

    # -- block registration + KV events ------------------------------------

    def _extract_block(self, page: int):
        """Device block [2, L, bs, Hkv, D] as a DEVICE array: the jit
        dispatch is async and the result is an independent staging buffer,
        so the block manager's offload path can defer the host transfer
        off-thread (np.asarray on the handle syncs when bytes are
        needed).  (Multihost: the sharded extract jit replicates its
        output, so that off-thread read stays collective-free.)"""
        return self._extract_jit(self.cache, np.int32(page))

    def _validate_block(self, data) -> None:
        """Loud mixed-mode guard on every injected block: a bf16 peer's
        block injected into an int8 cache (or vice versa) would bitcast
        garbage into live KV pages and corrupt decode silently.  The wire
        format carries dtype+shape (transfer.encode_block), so a
        kv-quant-mode mismatch between peers is detectable HERE, before
        any bytes touch the cache."""
        want_shape = self.cache_cfg.block_wire_shape
        got_shape = tuple(data.shape)
        got_int8 = jnp.dtype(data.dtype) == jnp.dtype(jnp.int8)
        # Float→float casts stay tolerated (an f32 test cache pulling a
        # bf16 block is a lossless-enough astype, and pre-quant code
        # allowed it); int8 packed blocks are NOT castable — only the
        # exact mode round-trips.
        if got_shape != want_shape or got_int8 != self.cache_cfg.quantized:
            raise ValueError(
                f"KV block format mismatch: peer sent "
                f"{jnp.dtype(data.dtype)}{list(got_shape)} but this cache "
                f"stores {jnp.dtype(self.cache_cfg.block_wire_dtype)}"
                f"{list(want_shape)} (kv_quant={self.cache_cfg.kv_quant!r})"
                " — prefill and decode workers must run the same "
                "--kv-quant mode; refusing to inject")

    def _inject_block(self, page: int, data) -> None:
        """Host array OR device array → device block (onboard /
        transfer-in).  A pulled device array arrives committed to one
        device; under a mesh it must be re-laid as replicated before the
        sharded inject scatters it into the cache's sharding (the
        tp=x→tp=y relayout's scatter half)."""
        self._validate_block(data)
        if (self.mesh is not None and isinstance(data, jax.Array)
                and not self._mh):
            # A no-op when the transfer plane already landed the block
            # on block_inject_sharding; a real relayout (the cross-mesh
            # scatter half) for anything else — replicated legacy pulls,
            # host-staged arrays committed to one device.
            data = jax.device_put(data, self.block_inject_sharding)
        self.cache = self._inject_jit(self.cache, np.int32(page),
                                      self._dev(data))

    def _on_block_evicted(self, block_hash: int) -> None:
        """Managed source evicted a block from G1 → router must forget it."""
        if self._kv_event_sink and self.config.enable_kv_events:
            self._emit(KvCacheEventData.removed([block_hash]))

    @hot_path
    def _publish_completed_blocks(self, req: Request) -> None:
        """Seal pages newly completed by this request: register them with
        the block source (future prefix hits) and emit STORED events."""
        events_on = (self._kv_event_sink is not None
                     and self.config.enable_kv_events)
        if not self._managed_cache and not events_on:
            return  # nobody consumes seals: skip the per-step hashing
        if req.prompt_embeds is not None:
            # Multimodal prompts hash their PLACEHOLDER tokens — sealing
            # them would prefix-match a different image's request.
            return
        if req.request_id not in self._requests:
            return  # already finished and dropped
        seq = self._hash_seqs.get(req.request_id)
        if seq is None:
            seq = TokenBlockSequence(block_size=self.block_size)
            self._hash_seqs[req.request_id] = seq
        if self._diffusion and req.state is RequestState.DECODE:
            # Committed AND emitted: whole blocks of what the stream holds
            # (a page is sealed only when every token in it is final).
            B = self.config.model.diffusion_block_length
            all_tokens = (req.prompt_tokens + req.output_tokens)[
                : req.total_len // B * B]
        else:
            all_tokens = (req.prompt_tokens[: req.prefilled]
                          + req.output_tokens)
        seq.extend(all_tokens[len(seq):])
        done = self._published_blocks.get(req.request_id, 0)
        complete = seq.blocks  # sealed blocks only
        if len(complete) <= done:
            return
        new = complete[done:]
        for bi, blk in enumerate(new, start=done):
            if bi < len(req.pages):
                self.allocator.register_block(req.pages[bi], blk.block_hash)
        if events_on:
            parent = complete[done - 1].block_hash if done else None
            self._emit(KvCacheEventData.stored(
                [b.block_hash for b in new], parent_hash=parent))
        self._published_blocks[req.request_id] = len(complete)
        if self.seal_sink is not None:
            # Prefill seal-progress stream (disagg eager KV streaming):
            # fires only when blocks actually sealed, and the sink is a
            # dict-lookup no-op unless a watcher registered this rid.
            self.seal_sink(req.request_id, len(complete))

    def _publish_removed_blocks(self, req: Request) -> None:
        if not self._kv_event_sink or not self.config.enable_kv_events:
            return
        seq = self._hash_seqs.get(req.request_id)
        done = self._published_blocks.get(req.request_id, 0)
        if not seq or not done:
            return
        hashes = [b.block_hash for b in seq.blocks[:done]]
        self._emit(KvCacheEventData.removed(hashes))

    def _emit(self, data: KvCacheEventData) -> None:
        self._event_id += 1
        self._kv_event_sink(KvCacheEvent(event_id=self._event_id, data=data))


class InferenceEngine:
    """Async facade: background step-loop thread + per-request streams.

    The event loop never touches the core directly: submissions and
    cancellations are enqueued under a micro-lock (never held across device
    work) and drained by the engine thread before each step, so a
    multi-second XLA compile inside step() cannot stall the event loop.
    """

    def __init__(self, core: EngineCore) -> None:
        self.core = core
        self._queues: Dict[str, asyncio.Queue] = {}
        self._seal_watchers: Dict[str, asyncio.Queue] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._cmd_lock = threading.Lock()
        self._pending_adds: List[tuple] = []
        self._pending_cancels: List[str] = []
        self._pending_calls: List[tuple] = []  # (fn, asyncio.Future)
        self._stop = threading.Event()
        self._wake = threading.Event()

    # -- lifecycle --------------------------------------------------------

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self.core.seal_sink = self._on_seal
        # Ownership transfer: the core (and its pools) may have been
        # built — and even stepped, e.g. warmup — on the constructing
        # thread; the step-loop thread owns them from here on
        # (DYNAMO_CONTRACTS thread-affinity pins re-pin on first call).
        contracts.release_owner(*self._contract_owned())
        self._thread = threading.Thread(
            target=self._run_loop, name="engine-step-loop", daemon=True)
        self._thread.start()

    def _contract_owned(self):
        """Everything whose @engine_thread_only pin must follow the step
        loop: the core, its allocator, and the tiered pools behind it."""
        owned = [self, self.core, self.core.allocator]
        manager = getattr(self.core.allocator, "manager", None)
        if manager is not None:
            owned += [manager, manager.device, manager.host, manager.disk]
        return [o for o in owned if o is not None]

    async def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread:
            await asyncio.to_thread(self._thread.join, 10.0)
        # What the run cost, left where an operator (or chip_smoke.py)
        # can read it after the process is gone.
        logger.info(
            "engine stopped: counters=%s peak_bytes_in_use=%s",
            json.dumps(self.core.counters.to_dict()),
            [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()])
        # The step loop is gone: release the thread-affinity pins so
        # tests may drive the core directly afterwards.
        contracts.release_owner(*self._contract_owned())
        # Tear down the managed block source's offload worker (thread
        # leak per discarded engine otherwise).
        close = getattr(getattr(self.core.allocator, "manager", None),
                        "close", None)
        if close is not None:
            await asyncio.to_thread(close)

    def _run_loop(self) -> None:
        contracts.register_engine_thread()
        enter = self.core.counters.enter
        self.core.counters.restart_phase_clock()
        try:
            while not self._stop.is_set():
                self._drain_commands()
                busy = self.core.has_work
                # step() leaves the clock in `deliver`, where the hand-off
                # to the asyncio loop below belongs.
                self._deliver(self.core.step(self._deliver) if busy else ())
                if not busy:
                    enter(PHASE_IDLE)
                    self._wake.wait(timeout=0.005)
                    self._wake.clear()
            # Shutdown: a block call still unread is read before the
            # thread that owns the device leaves.
            self.core.drain_block_call()
        finally:
            contracts.unregister_engine_thread()

    def _drain_commands(self) -> None:
        with self._cmd_lock:
            adds, self._pending_adds = self._pending_adds, []
            cancels, self._pending_cancels = self._pending_cancels, []
            calls, self._pending_calls = self._pending_calls, []
        if calls or adds or cancels:
            self.core.counters.enter(PHASE_COMMANDS)
        for fn, fut in calls:
            try:
                result = fn()
            except Exception as e:  # surfaced to the awaiting caller
                self._resolve(fut, None, e)
            else:
                self._resolve(fut, result, None)
        for rid, prompt, sampling, embeds, priority in adds:
            try:
                self.core.add_request(rid, prompt, sampling,
                                      prompt_embeds=embeds,
                                      priority=priority)
            except ValueError as e:
                self._dispatch(TokenDelta(
                    request_id=rid, token_ids=[], finished=True,
                    finish_reason=FinishReason.ERROR))
                logger.warning("rejecting request %s: %s", rid, e)
        for rid in cancels:
            self.core.cancel(rid)

    def _resolve(self, fut, result, exc) -> None:
        assert self._loop is not None

        def setter():
            if fut.done():
                return
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)

        self._loop.call_soon_threadsafe(setter)

    def _deliver(self, deltas) -> None:
        for d in deltas:
            self._dispatch(d)

    def _dispatch(self, delta: TokenDelta) -> None:
        q = self._queues.get(delta.request_id)
        if q is None:
            return
        assert self._loop is not None
        self._loop.call_soon_threadsafe(q.put_nowait, delta)

    # -- serving API ------------------------------------------------------

    @never_engine_thread
    async def generate(
        self,
        request_id: str,
        prompt_tokens: List[int],
        sampling: SamplingParams,
        prompt_embeds=None,
        priority: int = 1,
    ) -> AsyncIterator[TokenDelta]:
        """Submit and stream deltas until the request finishes.

        Cancellation: breaking out of / closing this generator cancels the
        request on the engine (reference disconnect semantics,
        `http/service/disconnect.rs`)."""
        q: asyncio.Queue = asyncio.Queue()
        self._queues[request_id] = q
        with self._cmd_lock:
            self._pending_adds.append((request_id, prompt_tokens, sampling,
                                       prompt_embeds, priority))
        self._wake.set()
        try:
            while True:
                delta = await q.get()
                yield delta
                if delta.finished:
                    return
        finally:
            self._queues.pop(request_id, None)
            with self._cmd_lock:
                self._pending_cancels.append(request_id)
            self._wake.set()

    # -- prefill seal-progress stream (disagg eager KV streaming) ---------

    @hot_path
    def _on_seal(self, request_id: str, sealed_blocks: int) -> None:
        """Engine-thread callback: forward a request's sealed-block
        high-water mark to its watcher.  A dict miss (no watcher — the
        overwhelmingly common case) is zero work, so the steady decode
        window pays nothing for the stream existing."""
        q = self._seal_watchers.get(request_id)
        if q is None or self._loop is None:
            return
        self._loop.call_soon_threadsafe(q.put_nowait, sealed_blocks)

    @never_engine_thread
    def watch_seals(self, request_id: str) -> asyncio.Queue:
        """Subscribe to a request's prefill progress: the returned queue
        yields the count of sealed (hash-registered) prompt blocks so
        far — what a disagg prefill worker publishes as incremental
        announcements so decode-side pullers can start streaming KV
        before the final done message."""
        q: asyncio.Queue = asyncio.Queue()
        self._seal_watchers[request_id] = q
        return q

    def unwatch_seals(self, request_id: str) -> None:
        self._seal_watchers.pop(request_id, None)

    @never_engine_thread
    async def run_in_engine(self, fn):
        """Run fn() on the engine thread between steps (cache access must
        never race the step loop); returns its result.  Awaiting this
        FROM the engine thread would deadlock (the engine thread is the
        one that drains the command), hence @never_engine_thread."""
        fut = asyncio.get_running_loop().create_future()
        with self._cmd_lock:
            self._pending_calls.append((fn, fut))
        self._wake.set()
        return await fut

    @never_engine_thread
    async def export_blocks(self, hashes) -> Dict[int, np.ndarray]:
        return await self.run_in_engine(
            lambda: self.core.export_blocks(hashes))

    @never_engine_thread
    async def clear_kv_blocks(self) -> int:
        return await self.run_in_engine(self.core.clear_prefix_cache)

    @never_engine_thread
    async def embed(self, token_lists) -> np.ndarray:
        # One engine-thread slot PER INPUT, not one for the whole batch:
        # decode steps for in-flight generations interleave between
        # items, so a large embeddings request can't head-of-line block
        # token streaming.
        rows = []
        for toks in token_lists:
            rows.append(await self.run_in_engine(
                lambda t=toks: self.core.embed_tokens([t])))
        return np.concatenate(rows, axis=0) if rows else np.zeros((0, 0))

    @never_engine_thread
    async def import_blocks(self, blocks) -> int:
        return await self.run_in_engine(
            lambda: self.core.import_blocks(blocks))

    @never_engine_thread
    async def resident_prefix_blocks(self, hashes) -> int:
        return await self.run_in_engine(
            lambda: self.core.resident_prefix_blocks(hashes))

    @never_engine_thread
    async def export_blocks_device(self, hashes,
                                   canonical: bool = True
                                   ) -> Dict[int, object]:
        return await self.run_in_engine(
            lambda: self.core.export_blocks_device(hashes,
                                                   canonical=canonical))

    @property
    def metrics(self) -> ForwardPassMetrics:
        return self.core.metrics
