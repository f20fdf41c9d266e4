"""Continuous-batching scheduler with XLA-friendly fixed shapes.

Semantics mirror what the reference's mocker models of vLLM
(`lib/llm/src/mocker/scheduler.rs` — watermark admission, chunked-prefill
token budget, block-per-page accounting) but drive a *real* engine; the
XLA twist is that every device step must hit a previously-compiled shape:

- decode runs at batch buckets (1, 2, 4, ... max_seqs), padding with null
  rows (seq_len 0, null block table) — one compiled program per bucket;
- prefill runs one sequence per step at chunk-length buckets (powers of
  two up to `max_prefill_chunk`), so a prompt of 1234 tokens costs
  ceil(1234/512) chunk steps of static shape;
- block tables have static width `max_pages` (covers `max_context`).

The scheduler itself is synchronous and deviceless — it only decides what
to run; the engine owns device arrays.  That makes admission/eviction
logic unit-testable at full speed (reference test strategy, SURVEY.md §4).
"""

from __future__ import annotations

import enum
import logging
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Sequence, Set

from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.runtime import flight_recorder
from dynamo_tpu.runtime.metrics import (
    BLOCKED_HELD, BLOCKED_PAGES, BLOCKED_SLOTS, REQUEST_STATES, RS_BUDGET_WAIT,
    RS_FIRST_TOKEN, RS_NONE, RS_PREEMPTED, RS_PREFILL, RS_WAITING,
    EngineStepCounters)

logger = logging.getLogger(__name__)


class FinishReason(str, enum.Enum):
    STOP = "stop"            # stop token / stop string hit
    LENGTH = "length"        # max_tokens or context limit
    CANCELLED = "cancelled"  # client disconnected / cancelled
    ERROR = "error"


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"


@dataclass
class Request:
    """One in-flight generation."""

    request_id: str
    prompt_tokens: List[int]
    sampling: SamplingParams
    state: RequestState = RequestState.WAITING
    # progress
    prefilled: int = 0                    # prompt tokens already processed
    output_tokens: List[int] = field(default_factory=list)
    pages: List[int] = field(default_factory=list)
    # A model with window layers: the window group's page of each block of
    # positions, like `pages` by position; 0 (the null block) where none is
    # held yet or the block lies behind the window and went back to its pool
    # (`Scheduler.ensure_window`).
    window_pages: List[int] = field(default_factory=list)
    slot: Optional[int] = None            # decode slot index while active
    finish_reason: Optional[FinishReason] = None
    # The request-state clock (runtime/metrics.py: EngineStepCounters.
    # request_state, which alone writes these): the state the request is
    # in (an `RS_*` index; RS_NONE off the clock), the `perf_counter_ns`
    # of its latest entry into each state (0: never entered) and the
    # nanoseconds it has spent in each state it has left.  The ledger's
    # stamps and the tracer's `engine.*` spans are differences within
    # `state_entry_ns` (EngineCore._first_token_timings).
    clock_state: int = RS_NONE
    state_entry_ns: List[int] = field(
        default_factory=lambda: [0] * len(REQUEST_STATES))
    state_ns: List[int] = field(
        default_factory=lambda: [0] * len(REQUEST_STATES))
    # `perf_counter_ns` at which its first token was appended (0: not yet).
    first_token_ns: int = 0
    # `EngineStepCounters.decode_dispatches` as the chunk that completed
    # its prompt was dispatched (-1: none was): the decode dispatch that
    # first holds its row tells from it whether a dispatch of the old
    # cohort went out in between (`cohort_joins`).
    decode_dispatches_at_prefill: int = -1
    # Tokens emitted before a preemption folded them into the prompt —
    # keeps max_tokens budgeting and seeded-RNG indices monotonic.
    prior_output: int = 0
    # Request-ledger scalars (runtime/ledger.py): the prompt tokens the
    # prefix cache served at admission (req.prefilled advances during
    # prefill, so the admission-time figure needs its own field) and how
    # many times this request was preempted (QoS or capacity) — both
    # ride the ledger's prefill stamp at first-token time.
    cached_prompt_tokens: int = 0
    preempts: int = 0
    # Memoized chained prompt-block hashes (admission retries must not
    # re-hash a long prompt every engine step); None = not yet computed.
    block_hashes: Optional[tuple] = None
    # Multimodal: [n, hidden] embeddings for prompt positions [0, n)
    # (placeholder token ids there); engine routes prefills carrying
    # these through the input-embeds step variant.
    prompt_embeds: Optional[object] = None
    # dp-attention locality: the allocator shard this request's pages
    # come from (derived from its slot at admission; None = shard-less).
    locality_shard: Optional[int] = None
    # QoS class (ISSUE 15): 0 = best-effort (preemptible under SLO burn,
    # held at admission while the budget burns), 1 = standard (default),
    # 2 = interactive.  Admission picks the highest class first (FCFS
    # within a class); capacity shortfalls preempt strictly-lower
    # classes before refusing a higher one.
    priority: int = 1

    @property
    def total_len(self) -> int:
        return len(self.prompt_tokens) + len(self.output_tokens)

    @property
    def context_len(self) -> int:
        """Prompt+output tokens the model has consumed.  All but the newest
        sampled token have KV in cache; the newest one's KV is written by
        the decode step that feeds it (at position context_len - 1)."""
        return self.prefilled + len(self.output_tokens)


class BlockAllocator:
    """Free-list page allocator over the paged cache (block 0 reserved null).

    The minimal block source: no prefix reuse (match always misses).  The
    engine normally uses the tiered, prefix-caching source
    (dynamo_tpu.llm.block_manager.engine_source.ManagedBlockSource), which
    duck-types this interface; this one remains for scheduler unit tests
    and reuse-free configurations.  Watermark semantics follow the
    reference mocker `KvManager`.

    `num_shards > 1` partitions blocks [1, num_blocks) into contiguous
    per-shard ranges (the dp-attention locality allocator: the cache's
    slot axis shards over tp in contiguous ranges, so a page is LOCAL to
    exactly one shard).  `allocate(n, shard=s)` draws strictly from
    shard s — locality is a correctness invariant for the local-attention
    decode path, so there is deliberately no cross-shard stealing; a
    shard running dry is an OOM for its rows (preempt semantics), exactly
    like a full replica."""

    def __init__(self, num_blocks: int, num_shards: int = 1) -> None:
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the null block)")
        if num_shards < 1 or (num_blocks % num_shards):
            raise ValueError(
                f"num_shards={num_shards} must divide num_blocks="
                f"{num_blocks} (contiguous slot ranges shard evenly)")
        self.num_blocks = num_blocks
        self.num_shards = num_shards
        self._shard_size = num_blocks // num_shards
        if num_shards == 1:
            self._free: List[int] = list(range(num_blocks - 1, 0, -1))
            self._shard_free: List[List[int]] = [self._free]
        else:
            # Shard s owns blocks [s*size, (s+1)*size); block 0 (null)
            # reduces shard 0's usable range by one.
            self._shard_free = [
                [b for b in range(min((s + 1) * self._shard_size,
                                      num_blocks) - 1,
                                  max(s * self._shard_size, 1) - 1, -1)]
                for s in range(num_shards)
            ]
            self._free = []  # unused in sharded mode (see properties)

    def shard_of_block(self, block: int) -> int:
        return block // self._shard_size

    # Prefix-cache interface (no-ops here).
    def prompt_hashes(self, prompt_tokens: Sequence[int]) -> tuple:
        return ()

    def match(self, prompt_tokens: Sequence[int], hashes=None):
        """Returns (cached_tokens, pinned_pages)."""
        return 0, []

    def register_block(self, page: int, block_hash: int) -> None:
        pass

    @property
    def free_blocks(self) -> int:
        return sum(len(f) for f in self._shard_free)

    def shard_free_blocks(self, shard: int) -> int:
        return len(self._shard_free[shard])

    @property
    def usage(self) -> float:
        usable = self.num_blocks - 1
        return 1.0 - self.free_blocks / usable

    def allocate(self, n: int, shard: Optional[int] = None) -> List[int]:
        if self.num_shards == 1:
            pool = self._shard_free[0]
        elif shard is None:
            # Shard-less callers (embeddings scratch etc.) take the
            # fullest pool — harmless, those pages are never decoded
            # through the local-attention path.
            pool = max(self._shard_free, key=len)
        else:
            pool = self._shard_free[shard]
        if n > len(pool):
            raise RuntimeError(
                f"out of KV blocks: want {n}, free {len(pool)}"
                + (f" in shard {shard}" if self.num_shards > 1 else ""))
        return [pool.pop() for _ in range(n)]

    def release(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p == 0:
                raise ValueError("attempt to free the null block")
            self._shard_free[self.shard_of_block(p)
                             if self.num_shards > 1 else 0].append(p)


def window_cap_blocks(window: int, chunk: int, block_size: int) -> int:
    """Blocks of a window group that cover a window and one chunk of
    positions, with a partly covered block at each end."""
    return -(-(window + chunk) // block_size) + 1


# The share of the device's seconds that decode keeps while prompts wait
# (the rule of mixed prefill, `EngineCore._chunk_rides`).
DECODE_SHARE = 0.85


@dataclass(frozen=True)
class SchedulerConfig:
    """Knobs, defaults sized like the reference mocker's
    (`mocker/protocols.rs:79-108`: 16384 blocks, block 64, 256 seqs,
    8192 batched tokens, watermark 0.01).

    Mixed prefill on the window path has one rule and no knob here: every
    dispatched decode window earns `(1 - DECODE_SHARE) / DECODE_SHARE` of
    its measured seconds as credit, and the planned chunk (whatever waits,
    up to `max_prefill_chunk`) rides behind a window once the credit covers
    the seconds its token bucket was measured to cost.  The rows decoding
    play no part.  `mixed_prefill_*` below bound the two paths that have
    no window clock: single-step engines and multihost ones."""

    max_seqs: int = 64
    max_prefill_chunk: int = 512
    max_batched_tokens: int = 8192
    block_size: int = 64
    max_pages_per_seq: int = 128          # static block-table width
    watermark: float = 0.01               # min free-block fraction to admit
    decode_buckets: tuple = (1, 2, 4, 8, 16, 32, 64)
    prefill_buckets: tuple = (16, 32, 64, 128, 256, 512)
    # Row buckets for PREFILL batches.  Distinct from decode_buckets: a
    # bounded mixed-step chunk is often a single row, and padding it to
    # the decode bucket (r5 first cut: 1 real row padded to 16 × 512
    # tokens = a full 8192-token device call for 512 useful tokens) made
    # every mixed step pay the whole-batch price the budget was supposed
    # to avoid.
    prefill_row_buckets: tuple = (1, 2, 4, 8, 16, 32, 64)
    # Prefill token cap per step WHILE decode sequences are running, for
    # the engines that dispatch chunk and decode step together: a
    # single-step engine (`decode_window` 1) and a multihost one.  An
    # unbounded mixed batch (up to max_batched_tokens) stalls every
    # in-flight stream for the whole batch; the cap trades prefill ramp
    # for steady ITL (reference: vLLM-style chunked prefill, mocker
    # `protocols.rs:97-98`).  A window engine on one host and the block
    # path lift it to `max_prefill_chunk` (`Scheduler.mixed_budget_override`)
    # and bound their chunks by other means (`EngineCore.step`).
    mixed_prefill_tokens: int = 256
    # Slack sizing: the mixed chunk additionally caps at
    # `mixed_prefill_per_row x n_decoding` tokens (floored at
    # `mixed_prefill_floor`), so chunk compute tracks the decode
    # window's own cost — a window over few rows is fast, and a
    # fixed-size chunk behind it would dominate the device's time
    # exactly when the decode fleet is most latency-sensitive.
    mixed_prefill_per_row: int = 4
    mixed_prefill_floor: int = 64
    # dp-attention locality: slot → allocator shard (engine-installed;
    # None = shard-less allocation).  A request's pages then come from
    # the cache range local to its decode rows' tp shard.
    shard_of_slot: Optional[Callable] = None
    # Packed ragged prefill (ISSUE 10): chunks pack into one flat token
    # axis (segments) instead of padded [R, T] rows.  Segment count per
    # pack is FIXED (one shape dim constant); the token axis snaps to
    # `packed_buckets()` — by default just (min(128, top), top) where
    # top covers max_prefill_chunk, so the prefill shape lattice is
    # (≤2 token buckets) × (page buckets) instead of rows × chunks ×
    # pages.  () = derive from prefill_buckets.
    packed_prefill_segments: int = 8
    packed_prefill_buckets: tuple = ()
    # Positions a decode step decides together (engine-installed from the
    # model: a block-diffusion model's block length; 1 = a causal model,
    # one token a sequence a step).  With B > 1 prefill covers the whole
    # blocks of a prompt only (its last `len % B` tokens open the first
    # generated block), chunks start and end on multiples of B (a block
    # sees all of itself, so it cannot straddle two chunks), and a
    # decoding sequence's table reaches a block ahead.
    token_block: int = 1

    def __post_init__(self):
        if self.max_seqs > max(self.decode_buckets):
            raise ValueError(
                f"max_seqs={self.max_seqs} exceeds largest decode bucket "
                f"{max(self.decode_buckets)}; padded arrays would overflow")
        if self.max_prefill_chunk > max(self.prefill_buckets):
            raise ValueError(
                f"max_prefill_chunk={self.max_prefill_chunk} exceeds largest "
                f"prefill bucket {max(self.prefill_buckets)}")
        if self.packed_prefill_buckets:
            # The pack builder promises an over-budget chunk "a pack of
            # its own", and the dispatch buffer is the top packed
            # bucket — so the top bucket must cover the align-rounded
            # max_prefill_chunk, and every bucket must satisfy the
            # kernel's PACK_ALIGN=8 sublane contract.  Validated here
            # so a bad config fails at construction, not as a numpy
            # broadcast error inside the hot serving loop.
            align = 8  # ops.pallas.PACK_ALIGN (not imported: no jax dep)
            bad = [b for b in self.packed_prefill_buckets if b % align]
            if bad:
                raise ValueError(
                    f"packed_prefill_buckets must be multiples of "
                    f"{align} (kernel PACK_ALIGN); got {bad}")
            need = -(-self.max_prefill_chunk // align) * align
            if need > max(self.packed_prefill_buckets):
                raise ValueError(
                    f"largest packed prefill bucket "
                    f"{max(self.packed_prefill_buckets)} cannot hold an "
                    f"aligned max_prefill_chunk ({need} tokens); raise "
                    "the bucket or lower max_prefill_chunk")
        if self.token_block > 1 and (
                self.block_size % self.token_block
                or self.max_prefill_chunk % self.token_block):
            # A full page then depends on nothing after it (prefix-cache
            # hashes stay sound) and a full chunk ends on a block edge.
            raise ValueError(
                f"token_block={self.token_block} must divide block_size="
                f"{self.block_size} and max_prefill_chunk="
                f"{self.max_prefill_chunk}")

    def prefill_target(self, prompt_len: int) -> int:
        """Prompt tokens prefill writes to the cache: all of them for a
        causal model, the whole blocks for a block-diffusion model."""
        tb = self.token_block
        return prompt_len if tb == 1 else prompt_len // tb * tb

    def decode_extent(self, req: "Request") -> int:
        """Positions a sequence's block table must cover for its next
        decode step: its context for a causal model, the end of the block
        it is about to denoise for a block-diffusion model."""
        tb = self.token_block
        if tb == 1:
            return req.context_len
        return req.total_len // tb * tb + tb

    def bucket_for_decode(self, n: int) -> int:
        for b in self.decode_buckets:
            if n <= b:
                return b
        return self.decode_buckets[-1]

    def with_decode_rows_every(self, step: int) -> "SchedulerConfig":
        """This configuration with a decode bucket at every multiple of
        `step` rows from 2 x step up to its largest bucket, beside those it
        has: for a model whose padding rows cost what live rows cost (each
        reads and writes a slot of recurrent state and is routed to
        experts), so that 17 rows do not step as 32."""
        top = max(self.decode_buckets)
        return replace(self, decode_buckets=tuple(sorted(
            set(self.decode_buckets) | set(range(2 * step, top, step)))))

    def bucket_for_prefill(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def bucket_for_prefill_rows(self, n: int) -> int:
        for b in self.prefill_row_buckets:
            if n <= b:
                return b
        return self.prefill_row_buckets[-1]

    def packed_buckets(self) -> tuple:
        """Token-axis buckets for packed ragged prefill.  Two by
        default: a small one so mixed-mode chunks behind decode windows
        don't pay a full-width program, and the top one covering
        max_prefill_chunk.  The whole packed shape set is these ×
        `page_bucket_ladder()` — what `--prewarm-prefill` compiles."""
        if self.packed_prefill_buckets:
            return tuple(sorted(self.packed_prefill_buckets))
        top = self.bucket_for_prefill(self.max_prefill_chunk)
        small = min(128, top)
        return (small, top) if small < top else (top,)

    def bucket_for_packed(self, n: int) -> int:
        for b in self.packed_buckets():
            if n <= b:
                return b
        return self.packed_buckets()[-1]

    def packed_prefill_budget(self) -> int:
        """Aligned-token capacity of one packed prefill dispatch."""
        return self.packed_buckets()[-1]

    def page_bucket_ladder(self) -> tuple:
        """Every value `bucket_for_pages` can return — the page-bucket
        half of the packed prefill shape set.  Probed through
        `bucket_for_pages` itself so the prewarm set can never desync
        from the buckets serving actually dispatches."""
        ladder = []
        n = 1
        while True:
            b = self.bucket_for_pages(n)
            if not ladder or b != ladder[-1]:
                ladder.append(b)
            if b >= self.max_pages_per_seq:
                return tuple(ladder)
            n = b + 1

    def bucket_for_pages(self, n: int) -> int:
        """Block-table width bucket: the device step's context gather costs
        O(width × block_size), so tables are sliced to the smallest
        power-of-two page count covering the batch — NOT the static
        max_pages width (that was an order-of-magnitude decode cliff at
        serving geometry: every step paid for max_context regardless of
        actual context)."""
        b = 2
        while b < n:
            b *= 2
        return min(b, self.max_pages_per_seq)


def pack_prefill_chunks(items: List["PrefillWork"], budget: int,
                        max_segments: int,
                        align: int = 8) -> List[List["PrefillWork"]]:
    """Size packed ragged prefill dispatches to a token budget.

    Greedy in-order (FCFS — the plan's item order is admission order)
    first-fit: each pack holds at most `max_segments` chunks whose
    `align`-rounded lengths sum to at most `budget` tokens (align is the
    kernel's PACK_ALIGN sublane contract — segment starts land on
    8-token boundaries).  A chunk longer than the budget still gets a
    pack of its own (chunk lengths are capped at max_prefill_chunk ≤ the
    top packed bucket, so this only triggers on degenerate configs)."""
    packs: List[List[PrefillWork]] = []
    cur: List[PrefillWork] = []
    cur_tokens = 0
    for w in items:
        need = -(-w.length // align) * align
        if cur and (cur_tokens + need > budget
                    or len(cur) >= max_segments):
            packs.append(cur)
            cur, cur_tokens = [], 0
        cur.append(w)
        cur_tokens += need
    if cur:
        packs.append(cur)
    return packs


@dataclass
class PrefillWork:
    """One prefill chunk for one sequence."""

    request: Request
    start: int        # absolute position of chunk start
    length: int       # real tokens in chunk


@dataclass
class PrefillBatch:
    """All of this iteration's prefill chunks, packed into ONE device call
    (ragged rows padded to `chunk`): N concurrent prompts cost one dispatch,
    not N (r1 ran one sequence per call — TTFT under concurrency died)."""

    items: List[PrefillWork]
    rows: int         # padded row count (batch bucket)
    chunk: int        # padded chunk length (token bucket)
    pages: int        # padded block-table width (page bucket)


@dataclass
class DecodeWork:
    """One decode step over all decoding sequences (padded to bucket)."""

    requests: List[Request]
    bucket: int
    pages: int        # padded block-table width (page bucket)


@dataclass
class StepPlan:
    prefill: Optional[PrefillBatch]
    decode: Optional[DecodeWork]

    @property
    def empty(self) -> bool:
        return self.prefill is None and self.decode is None


class Scheduler:
    """Decides, each engine iteration, which chunks to run."""

    def __init__(self, config: SchedulerConfig, allocator: BlockAllocator,
                 window_allocator: Optional[BlockAllocator] = None,
                 window: int = 0) -> None:
        self.config = config
        self.allocator = allocator
        # A model with window layers: the pool of its window group's pages
        # and the window's length in positions.  A sequence holds that
        # group's blocks for the positions its window layers can still read
        # and for the chunk being written, `window_cap` of them at most.
        self.window_allocator = window_allocator
        self.window = window
        self.window_released = 0          # blocks given back behind a window
        self.waiting: List[Request] = []
        self.running: List[Request] = []       # PREFILL or DECODE
        self._slots: List[Optional[Request]] = [None] * config.max_seqs
        # Cumulative admission prefix-match accounting (tokens): hit =
        # prompt tokens whose prefill the cache skipped, miss = tokens
        # that had to be computed.  The engine derives
        # gpu_prefix_cache_hit_rate (ForwardPassMetrics) from these and
        # KvCacheMetrics exports them as
        # dynamo_kv_prefix_cache_{hits,misses}_tokens.  Re-admissions
        # after preemption recount — each admission is a real lookup.
        self.prefix_hit_tokens = 0
        self.prefix_miss_tokens = 0
        # Flight-recorder breadcrumbs for the scheduling decisions the
        # postmortem needs ordered (admissions, preemptions); the module
        # singleton is a no-op until the process enables recording.
        self.flight = flight_recorder.get_recorder()
        # Engine-installed, once: the prefill tokens a plan may hold while
        # sequences decode, in place of the static mixed_prefill_tokens /
        # per-row slack caps.  None = the static caps.
        self.mixed_budget_override: Optional[int] = None
        # QoS pressure (ISSUE 15 leg 3): `qos_pressure_fn() -> float` is
        # the SLO monitor's worst fast-window burn rate (worker wires
        # `SloMonitor.last_max_burn`); at or above `qos_threshold` the
        # error budget is actively burning — best-effort (priority <= 0)
        # admissions hold, and running best-effort requests are shed one
        # per plan() while a higher class waits.  `qos_preempt_sink(req)`
        # executes the preempt (the engine's _qos_preempt: recompute
        # preemption + sealed-block demotion to the host tier); a bare
        # scheduler without a sink falls back to plain preempt().
        self.qos_pressure_fn: Optional[Callable[[], float]] = None
        self.qos_threshold: float = 1.0
        self.qos_preempt_sink: Optional[Callable[[Request], None]] = None
        self.qos_preemptions = 0          # cumulative victims
        self.qos_active = False           # pressure state at last plan()
        # The request-state clock's owner (the engine installs its own
        # counters here; a bare scheduler keeps this one).  Every state
        # from `waiting` to `first_token`, `preempted` and the leaving of
        # the clock begin in this file; `cohort_wait` and `decode` in the
        # engine, which alone knows of tokens and dispatches.
        self.counters = EngineStepCounters()

    # -- admission --------------------------------------------------------

    def add_request(self, req: Request) -> None:
        max_ctx = self.config.max_pages_per_seq * self.config.block_size
        if len(req.prompt_tokens) + req.sampling.max_tokens > max_ctx:
            req.state = RequestState.FINISHED
            req.finish_reason = FinishReason.LENGTH
            return
        self.waiting.append(req)
        self.counters.request_state(req, RS_WAITING)

    def _pages_needed(self, tokens: int) -> int:
        return (tokens + self.config.block_size - 1) // self.config.block_size

    # -- the window group -------------------------------------------------

    @property
    def window_cap(self) -> int:
        """The most window-group blocks one sequence holds: the window and
        one chunk of positions, and a partly covered block at each end."""
        return window_cap_blocks(self.window, self.config.max_prefill_chunk,
                                 self.config.block_size)

    def ensure_window(self, req: Request, start: int, end: int) -> bool:
        """The window group's pages for a dispatch whose first query stands
        at position `start` and which writes positions up to `end`: blocks
        in which every position is a window or more behind `start` go back
        to their pool first (what was dispatched before this reads them
        before anything dispatched after it can write them: one device, in
        order), then the blocks up to `end` that are not held yet are
        taken.  False if the pool runs dry (nothing is taken then)."""
        pool = self.window_allocator
        if pool is None:
            return True
        bs = self.config.block_size
        pages = req.window_pages
        dead = min(max(0, (start - self.window + 1) // bs), len(pages))
        gone = [p for p in pages[:dead] if p]
        if gone:
            pool.release(gone)
            pages[:dead] = [0] * dead
            self.window_released += len(gone)
        need = self._pages_needed(end)
        pages.extend([0] * (need - len(pages)))
        want = [b for b in range(dead, need) if not pages[b]]
        if len(want) > pool.free_blocks:
            return False
        for b, page in zip(want, pool.allocate(len(want))):
            pages[b] = page
        return True

    def _release_window(self, req: Request) -> None:
        held = [p for p in req.window_pages if p]
        if held:
            self.window_allocator.release(held)
        req.window_pages = []

    def _qos_pressure(self) -> bool:
        """True while the installed SLO burn signal is at or above the
        QoS threshold (a broken/missing signal reads as no pressure —
        QoS must never wedge admission)."""
        fn = self.qos_pressure_fn
        if fn is None:
            return False
        try:
            burn = fn()
        except Exception:
            return False
        return burn is not None and burn >= self.qos_threshold

    def _next_admit_index(self, pressure: bool) -> Optional[int]:
        """Waiting index to admit next: highest priority class first,
        FCFS within a class; under SLO-burn pressure best-effort
        (priority <= 0) requests hold in the queue."""
        best = None
        best_p = None
        for i, r in enumerate(self.waiting):
            if pressure and r.priority <= 0:
                continue
            if best is None or r.priority > best_p:
                best, best_p = i, r.priority
        return best

    def _qos_victim(self, min_priority: int) -> Optional[Request]:
        """Newest running request of the lowest class strictly below
        `min_priority` — the least-progressed work of the most
        preemptible class."""
        victims = [r for r in self.running if r.priority < min_priority]
        if not victims:
            return None
        low = min(r.priority for r in victims)
        return [r for r in victims if r.priority == low][-1]

    def _qos_preempt(self, req: Request) -> None:
        """Execute one QoS preemption through the engine's sink (which
        resets seal bookkeeping and demotes the victim's sealed KV to
        the host tier); a bare scheduler preempts in place."""
        self.qos_preemptions += 1
        sink = self.qos_preempt_sink
        if sink is not None:
            sink(req)
        else:
            self.preempt(req)

    def _qos_shed(self) -> None:
        """SLO burn at/above threshold: shed ONE running best-effort
        request per plan() — bounded work — but only while a higher
        class is actually in the machine or waiting for it (an
        all-best-effort fleet has nobody to yield to; parking it would
        just idle the hardware)."""
        if not (any(r.priority > 0 for r in self.waiting)
                or any(r.priority > 0 for r in self.running)):
            return
        victims = [r for r in self.running if r.priority <= 0]
        if victims:
            self._qos_preempt(victims[-1])

    def _try_admit(self) -> None:
        usable = self.allocator.num_blocks - 1
        pressure = self.qos_active
        counters = self.counters
        # Why the loop was left with requests still queued (the while
        # condition's own exit is a full `running`: no slot).
        blocked = BLOCKED_SLOTS
        while self.waiting and len(self.running) < self.config.max_seqs:
            idx = self._next_admit_index(pressure)
            if idx is None:
                blocked = BLOCKED_HELD
                break  # only held best-effort requests remain queued
            req = self.waiting[idx]
            slot = next(
                (i for i, s in enumerate(self._slots) if s is None), None)
            if slot is None:
                break
            # Prefix-cache match first: cached pages are reused (pinned),
            # only the remainder needs fresh allocation.
            if req.block_hashes is None:
                req.block_hashes = self.allocator.prompt_hashes(
                    req.prompt_tokens)
            cached_tokens, cached_pages = self.allocator.match(
                req.prompt_tokens, req.block_hashes)
            need_total = self._pages_needed(len(req.prompt_tokens) + 1)
            need_new = max(0, need_total - len(cached_pages))
            shard = (self.config.shard_of_slot(slot)
                     if self.config.shard_of_slot else None)
            # Admit only if the new pages fit and leave the watermark
            # (per-shard capacity when locality is on: a full shard is a
            # full replica from its rows' point of view).
            free_here = (self.allocator.shard_free_blocks(shard)
                         if shard is not None
                         and getattr(self.allocator, "num_shards", 1) > 1
                         else self.allocator.free_blocks)
            short = free_here - need_new < self.config.watermark * usable
            if not short and self.window_allocator is not None:
                # Both groups count: the window group's first blocks (as
                # many as the prompt has, `window_cap` at most) are taken
                # at admission, so that what is admitted can run.
                short = (min(need_total, self.window_cap)
                         > self.window_allocator.free_blocks)
            if short:
                if cached_pages:
                    self.allocator.release(cached_pages)
                # Priority preemption: a capacity-blocked higher class
                # displaces the newest strictly-lower-class request (its
                # sealed KV demotes down-tier via the engine sink) and
                # the admission retries with the freed pages.
                victim = self._qos_victim(req.priority)
                if victim is not None:
                    self._qos_preempt(victim)
                    continue
                # Nothing running means nothing will ever free pages — the
                # head request can never fit; fail it instead of spinning.
                if not self.running:
                    self.waiting.pop(idx)
                    req.state = RequestState.FINISHED
                    req.finish_reason = FinishReason.LENGTH
                    counters.request_state(req, RS_NONE)
                blocked = BLOCKED_PAGES
                break
            self.waiting.pop(idx)
            req.locality_shard = shard
            req.pages = list(cached_pages) + self._allocate(need_new, shard)
            if self.window_allocator is not None:
                self.ensure_window(req, 0, min(
                    len(req.prompt_tokens) + 1,
                    self.window_cap * self.config.block_size))
            # Cached prefix skips prefill compute, but at least the last
            # prompt token is always recomputed so admission yields logits.
            if self.config.token_block == 1:
                req.prefilled = min(cached_tokens,
                                    len(req.prompt_tokens) - 1)
            else:
                # No logits are owed at admission: the first block's
                # forwards give them.  Cached pages hold whole blocks.
                req.prefilled = min(cached_tokens, self.config.prefill_target(
                    len(req.prompt_tokens)))
            req.cached_prompt_tokens = req.prefilled
            self.prefix_hit_tokens += req.prefilled
            self.prefix_miss_tokens += len(req.prompt_tokens) - req.prefilled
            req.slot = slot
            self._slots[slot] = req
            req.state = RequestState.PREFILL
            # A preempted request stays `preempted` through its requeue
            # and re-prefill, until its next decode dispatch.
            fresh = req.clock_state == RS_WAITING
            now = (counters.request_state(req, RS_BUDGET_WAIT)
                   if fresh else 0)
            if req.prefilled >= self.config.prefill_target(
                    len(req.prompt_tokens)):
                # Nothing to prefill (a prompt shorter than a block, or
                # every whole block cached): straight to its first block,
                # through `prefill` in no time.
                req.state = RequestState.DECODE
                if fresh:
                    counters.request_state(req, RS_PREFILL, now)
                    counters.request_state(req, RS_FIRST_TOKEN, now)
            self.running.append(req)
            fl = self.flight
            if fl.enabled:
                fl.record("admit", rid=req.request_id,
                          prompt=len(req.prompt_tokens),
                          cached=cached_tokens, new_pages=need_new)
        counters.set_admit_blocked(blocked if self.waiting else RS_NONE)

    # -- page growth ------------------------------------------------------

    def _allocate(self, n: int, shard: Optional[int]) -> List[int]:
        """Allocator call, shard-aware when both sides support it (the
        managed tiered source has no shard concept — locality mode runs
        with the plain allocator)."""
        if shard is not None and getattr(self.allocator,
                                         "num_shards", 1) > 1:
            return self.allocator.allocate(n, shard=shard)
        return self.allocator.allocate(n)

    def ensure_capacity(self, req: Request, new_len: int) -> bool:
        """Grow req's page list to cover new_len tokens; False if OOM."""
        need = self._pages_needed(new_len)
        if need > self.config.max_pages_per_seq:
            return False
        shard = req.locality_shard
        sharded = (shard is not None
                   and getattr(self.allocator, "num_shards", 1) > 1)
        while len(req.pages) < need:
            free = (self.allocator.shard_free_blocks(shard) if sharded
                    else self.allocator.free_blocks)
            if free == 0:
                return False
            req.pages.extend(self._allocate(1, shard))
        # The window group beside it: the next query stands at the newest
        # token the host knows of (dispatches not read yet stand further on:
        # the later position lets go of no more than this one).
        return self.ensure_window(req, max(req.context_len - 1, 0), new_len)

    # -- planning ---------------------------------------------------------

    def plan(self) -> StepPlan:
        """Build this iteration's work under the batched-token budget.

        Decode-first (latency): all DECODE sequences take one step; the
        remaining token budget goes to prefill chunks, longest-waiting
        first (FCFS, like the reference mocker)."""
        self.qos_active = self._qos_pressure()
        if self.qos_active:
            self._qos_shed()
        self._try_admit()
        bs = self.config.block_size

        budget = self.config.max_batched_tokens
        decoding = [r for r in self.running if r.state is RequestState.DECODE]
        decode = None
        if decoding:
            # Width covers the context each row will have AFTER this step's
            # page growth (ensure_capacity grows to ceil(context_len/bs));
            # rows may hold extra pre-allocated pages beyond that — the
            # engine clips the row fill, the gather never reads past
            # seq_len anyway.
            decode = DecodeWork(
                requests=decoding,
                bucket=self.config.bucket_for_decode(len(decoding)),
                pages=self.config.bucket_for_pages(max(
                    (self.config.decode_extent(r) + bs - 1) // bs
                    for r in decoding)),
            )
            budget -= len(decoding)
            # Interference bound: with streams decoding, prefill gets at
            # most mixed_prefill_tokens this step, shrunk further to
            # track the decode fleet's own step cost (see SchedulerConfig
            # mixed_prefill_per_row), unless the engine lifted the cap.
            if self.mixed_budget_override is not None:
                budget = min(budget, max(0, self.mixed_budget_override))
            else:
                slack = max(self.config.mixed_prefill_floor,
                            self.config.mixed_prefill_per_row * len(decoding))
                budget = min(budget, self.config.mixed_prefill_tokens, slack)

        items: List[PrefillWork] = []
        for req in self.running:
            if req.state is not RequestState.PREFILL:
                continue
            if budget <= 0 or len(items) >= self.config.max_seqs:
                break
            remaining = self.config.prefill_target(
                len(req.prompt_tokens)) - req.prefilled
            chunk = min(remaining, self.config.max_prefill_chunk, budget)
            if chunk < remaining:
                chunk -= chunk % self.config.token_block
            if chunk <= 0:
                continue
            if not self.ensure_window(req, req.prefilled,
                                      req.prefilled + chunk):
                continue    # waits for a sequence to let window pages go
            if req.clock_state == RS_BUDGET_WAIT:
                self.counters.request_state(req, RS_PREFILL)
            items.append(PrefillWork(
                request=req, start=req.prefilled, length=chunk))
            budget -= chunk
        prefill = None
        if items:
            prefill = PrefillBatch(
                items=items,
                rows=self.config.bucket_for_prefill_rows(len(items)),
                chunk=self.config.bucket_for_prefill(
                    max(w.length for w in items)),
                pages=self.config.bucket_for_pages(max(
                    (w.start + w.length + bs - 1) // bs for w in items)),
            )
        return StepPlan(prefill=prefill, decode=decode)

    # -- preemption -------------------------------------------------------

    def preempt(self, req: Request) -> None:
        """Release the request's pages and requeue it (front of line) for
        recompute.  Generated tokens fold into the prompt: the recompute
        prefill rebuilds their KV, and completion of that prefill samples
        the next token exactly as if decode had continued.  (vLLM-style
        recompute preemption; the reference delegates this to its engines.)"""
        req.preempts += 1
        self.counters.request_state(req, RS_PREEMPTED)
        fl = self.flight
        if fl.enabled:
            fl.record("sched_preempt", rid=req.request_id,
                      output=len(req.output_tokens),
                      pages=len(req.pages))
        if req in self.running:
            self.running.remove(req)
        if req.slot is not None:
            self._slots[req.slot] = None
            req.slot = None
        if req.pages:
            self.allocator.release(req.pages)
            req.pages = []
        self._release_window(req)
        req.prior_output += len(req.output_tokens)
        req.prompt_tokens = req.prompt_tokens + req.output_tokens
        req.output_tokens = []
        req.prefilled = 0
        req.block_hashes = None  # prompt changed: re-hash on re-admission
        req.state = RequestState.WAITING
        self.waiting.insert(0, req)

    # -- completion callbacks --------------------------------------------

    def prefill_done(self, work: PrefillWork) -> None:
        req = work.request
        req.prefilled += work.length
        if req.prefilled >= self.config.prefill_target(
                len(req.prompt_tokens)):
            req.state = RequestState.DECODE
            if req.clock_state == RS_PREFILL:
                self.counters.request_state(req, RS_FIRST_TOKEN)

    def finish(self, req: Request, reason: FinishReason) -> None:
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        self.counters.request_state(req, RS_NONE)
        if req in self.running:
            self.running.remove(req)
        if req in self.waiting:
            self.waiting.remove(req)
        if req.slot is not None:
            self._slots[req.slot] = None
            req.slot = None
        if req.pages:
            self.allocator.release(req.pages)
            req.pages = []
        self._release_window(req)

    @property
    def num_active(self) -> int:
        return len(self.running) + len(self.waiting)
