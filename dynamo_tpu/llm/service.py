"""Model manager + the engine-client seam.

`EngineClient` is the streaming contract everything composes through — the
analog of the reference's `AsyncEngine` trait (`lib/runtime/src/engine.rs:
207`: `generate(SingleIn<Req>) -> ManyOut<Resp>`).  A local engine, a
KV-routed remote pool, and a mock engine all implement it, so the HTTP
frontend doesn't know which it's talking to (reference EngineConfig
{StaticFull, Dynamic} assembly, `entrypoint/input/common.rs:183`).

`ModelManager` is the frontend's model registry (reference
`discovery/model_manager.rs:33`): models appear/disappear at runtime as
workers register/deregister.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import AsyncIterator, Dict, List, Optional, Protocol

from dynamo_tpu.engine.engine import InferenceEngine, TokenDelta
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor, PreprocessedRequest
from dynamo_tpu.llm.tokenizer import Tokenizer


class EngineClient(Protocol):
    """Streaming generate contract (AsyncEngine analog)."""

    def generate(
        self, request: PreprocessedRequest
    ) -> AsyncIterator[TokenDelta]: ...


# QoS classes (ISSUE 15): the frontend's x-dynamo-priority header (or a
# router/operator annotation) rides the request's annotations dict under
# this key; the worker resolves it to the scheduler's integer class.
PRIORITY_ANNOTATION = "priority"
PRIORITY_CLASSES = {"best_effort": 0, "best-effort": 0, "batch": 0,
                    "standard": 1, "default": 1,
                    "interactive": 2, "realtime": 2}


def priority_of(request) -> int:
    """Scheduler priority from a request's `priority` annotation: a
    named class or a bare integer; anything malformed (version-skewed
    frontend) is standard — never fail a request over QoS metadata."""
    raw = request.annotations.get(PRIORITY_ANNOTATION)
    if raw is None:
        return 1
    raw = str(raw).strip().lower()
    if raw in PRIORITY_CLASSES:
        return PRIORITY_CLASSES[raw]
    try:
        return max(0, min(2, int(raw)))
    except ValueError:
        return 1


class LocalEngineClient:
    """EngineClient over an in-process InferenceEngine."""

    def __init__(self, engine: InferenceEngine) -> None:
        self._engine = engine

    async def generate(
        self, request: PreprocessedRequest
    ) -> AsyncIterator[TokenDelta]:
        import time as _time

        from dynamo_tpu.runtime import tracing
        from dynamo_tpu.runtime.ledger import ledger_of

        # Bind the serving task's span to the request id so engine-thread
        # spans (admission→first-token) parent under it — the in-process
        # analog of engine_wire_handler's worker-side binding.
        tracer = tracing.get_tracer()
        span = tracing.current_span()
        if span is not None:
            tracer.bind(request.request_id, span.ctx)
        # Request-ledger stamps (runtime/ledger.py), all ON THIS event
        # loop: the engine's phases from the timings the core attached to
        # the delta of the first token and to the last one (the request-
        # state clock's stamps), plus a per-token decode interval summary
        # accumulated here — the engine thread and its EngineStepCounters
        # never see a ledger.
        led = ledger_of(request)
        n_intervals = 0
        interval_sum = 0.0
        interval_max = 0.0
        last_t: Optional[float] = None
        try:
            async for delta in self._engine.generate(
                    request.request_id, request.token_ids, request.sampling,
                    prompt_embeds=request.prompt_embeds,
                    priority=priority_of(request)):
                if led is not None and delta.token_ids:
                    now = _time.monotonic()
                    if last_t is not None:
                        gap = now - last_t
                        n_intervals += 1
                        interval_sum += gap
                        interval_max = max(interval_max, gap)
                    last_t = now
                if led is not None and delta.timings is not None:
                    self._stamp_engine_timings(led, delta.timings)
                if led is not None and delta.finished and n_intervals:
                    led.stamp("decode", dur=interval_sum, n=n_intervals,
                              max_s=round(interval_max, 6))
                yield delta
        finally:
            tracer.unbind(request.request_id)

    @staticmethod
    def _stamp_engine_timings(led, t: dict) -> None:
        """Engine-phase stamps from a delta's `timings`.  With the first
        token: queue (arrival -> admission: no slot, no pages, or held),
        budget_wait (admission -> first chunk planned), prefill (-> last
        chunk done, with cached-token and preemption attrs) and
        first_token (-> first token emitted) tile the engine's share of
        TTFT.  With the last delta: cohort_wait (first token -> first
        decode dispatch that held the row) and, where there was one,
        preempted (preemption -> next decode dispatch), both inside the
        `decode` summary's span."""
        if "first_token" in t:
            led.stamp("queue", dur=t["admitted"] - t["arrival"],
                      t=t["admitted"])
            led.stamp("budget_wait", dur=t["prefill_start"] - t["admitted"],
                      t=t["prefill_start"])
            led.stamp("prefill", dur=t["prefill_end"] - t["prefill_start"],
                      t=t["prefill_end"], prompt_tokens=t["prompt_tokens"],
                      cached_tokens=t["cached_tokens"],
                      preempts=t["preempts"])
            led.stamp("first_token", dur=t["first_token"] - t["prefill_end"],
                      t=t["first_token"])
        if "cohort_wait_s" in t:
            led.stamp("cohort_wait", dur=t["cohort_wait_s"])
            if t["preempted_s"] > 0:
                led.stamp("preempted", dur=t["preempted_s"])

    async def embed(self, token_lists):
        """Last-token hidden-state embeddings: [n, hidden] (the
        /v1/embeddings engine surface)."""
        return await self._engine.embed(token_lists)

    async def clear_kv_blocks(self) -> int:
        return await self._engine.clear_kv_blocks()


@dataclass
class ModelHandle:
    """Everything the frontend needs to serve one model."""

    name: str
    tokenizer: Tokenizer
    preprocessor: OpenAIPreprocessor
    client: EngineClient
    # Context ceiling for boundary validation (reference validate.rs);
    # requests whose prompt alone exceeds it get a 400, and max_tokens is
    # clamped to fit.
    max_context: int = 8192
    # Multimodal hook (llm/multimodal.MultimodalAttach): image_url chat
    # parts → prompt_embeds; None = text-only model.
    multimodal: Optional[object] = None


class ModelManager:
    def __init__(self) -> None:
        self._models: Dict[str, ModelHandle] = {}

    def register(self, handle: ModelHandle) -> None:
        self._models[handle.name] = handle

    def remove(self, name: str) -> Optional[ModelHandle]:
        return self._models.pop(name, None)

    def get(self, name: str) -> Optional[ModelHandle]:
        return self._models.get(name)

    def names(self) -> List[str]:
        return sorted(self._models)

    def __len__(self) -> int:
        return len(self._models)
