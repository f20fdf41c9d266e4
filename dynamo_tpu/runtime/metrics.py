"""Prometheus-format metrics registry.

Role of the reference's `lib/runtime/src/metrics.rs` (hierarchical names
drt→namespace→component→endpoint) and `lib/llm/src/http/service/metrics.rs`
(the TTFT/ITL histograms the SLA planner scrapes —
`*_time_to_first_token_seconds`, `*_inter_token_latency_seconds`).  Those
exact series names are load-bearing: the planner's Prometheus queries key
on them (reference `planner/utils/prometheus.py`), so our planner does too.

Self-contained text-format exposition (no prometheus_client dependency);
thread-safe; histograms use fixed buckets chosen for LLM latencies.
"""

from __future__ import annotations

import logging
import math
import threading
import time
from bisect import bisect_right
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from dynamo_tpu.runtime import flight_recorder
from dynamo_tpu.runtime.contracts import hot_path, never_engine_thread
from dynamo_tpu.runtime.logutil import warn_rate_limited

_logger = logging.getLogger(__name__)

# Buckets tuned for token-level latencies (seconds): sub-ms resolution at
# the bottom (a routing decision or in-process TPOT at speedup is ~100 µs)
# through 60 s at the top (a cold-compile TTFT).
LATENCY_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Optional[Dict[str, str]]) -> LabelKey:
    return tuple(sorted((labels or {}).items()))


def _escape_label_value(v: str) -> str:
    """Prometheus text-exposition escaping: inside a label value, `\\`,
    `"` and newline must be escaped or the whole exposition is invalid
    (a scraper rejects every series, not just the bad one)."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(key: LabelKey, extra: str = "") -> str:
    parts = [f'{k}="{_escape_label_value(v)}"' for k, v in key]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class Counter:
    def __init__(self, name: str, help_: str) -> None:
        self.name, self.help = name, help_
        self._values: Dict[LabelKey, float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, labels: Optional[Dict[str, str]] = None):
        k = _label_key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + amount

    def value(self, labels: Optional[Dict[str, str]] = None) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        with self._lock:  # concurrent inc() must not tear the snapshot
            values = sorted(self._values.items())
        for k, v in values:
            out.append(f"{self.name}{_fmt_labels(k)} {v}")
        return out


class Gauge:
    def __init__(self, name: str, help_: str) -> None:
        self.name, self.help = name, help_
        self._values: Dict[LabelKey, float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, labels: Optional[Dict[str, str]] = None):
        with self._lock:
            self._values[_label_key(labels)] = float(value)

    def add(self, amount: float, labels: Optional[Dict[str, str]] = None):
        k = _label_key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + amount

    def value(self, labels: Optional[Dict[str, str]] = None) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        with self._lock:  # concurrent set()/add() must not tear the snapshot
            values = sorted(self._values.items())
        for k, v in values:
            out.append(f"{self.name}{_fmt_labels(k)} {v}")
        return out


class Histogram:
    def __init__(self, name: str, help_: str,
                 buckets: Sequence[float] = LATENCY_BUCKETS) -> None:
        self.name, self.help = name, help_
        self.buckets = tuple(sorted(buckets))
        self._counts: Dict[LabelKey, List[int]] = {}
        self._sum: Dict[LabelKey, float] = {}
        self._total: Dict[LabelKey, int] = {}
        self._lock = threading.Lock()

    def observe(self, value: float, labels: Optional[Dict[str, str]] = None):
        k = _label_key(labels)
        idx = bisect_right(self.buckets, value)
        with self._lock:
            if k not in self._counts:
                self._counts[k] = [0] * (len(self.buckets) + 1)
                self._sum[k] = 0.0
                self._total[k] = 0
            self._counts[k][idx] += 1
            self._sum[k] += value
            self._total[k] += 1

    def count(self, labels: Optional[Dict[str, str]] = None) -> int:
        return self._total.get(_label_key(labels), 0)

    def sum(self, labels: Optional[Dict[str, str]] = None) -> float:
        return self._sum.get(_label_key(labels), 0.0)

    def mean(self, labels: Optional[Dict[str, str]] = None) -> float:
        """NaN on an empty label set (never raises): 0.0 read as "zero
        latency" by the SLA planner's arithmetic; NaN propagates as
        "no data" and comparisons against it are False."""
        k = _label_key(labels)
        with self._lock:  # count and sum must come from one snapshot
            n = self._total.get(k, 0)
            s = self._sum.get(k, 0.0)
        return s / n if n else float("nan")

    # -- label-aggregated views (SLO burn-rate sources) --------------------

    def total_count(self) -> int:
        """Observations across ALL label sets."""
        with self._lock:
            return sum(self._total.values())

    def total_sum(self) -> float:
        with self._lock:
            return sum(self._sum.values())

    def total_mean(self) -> float:
        """Mean across all label sets; NaN when empty (same "no data"
        propagation contract as `mean`)."""
        with self._lock:
            n = sum(self._total.values())
            s = sum(self._sum.values())
        return s / n if n else float("nan")

    def count_le(self, value: float) -> int:
        """Observations known to be <= `value`, across all label sets —
        the cumulative count at the largest bucket bound <= `value`
        (matching the `le` cumulative the exposition prints).  Bucket
        granularity: observations in the bucket CONTAINING a mid-bucket
        `value` are excluded (conservative for SLO accounting — they
        count as bad); pick thresholds at bucket bounds for exactness."""
        idx = bisect_right(self.buckets, value)
        with self._lock:
            return sum(sum(c[:idx]) for c in self._counts.values())

    def quantile(self, q: float, labels: Optional[Dict[str, str]] = None) -> float:
        """Approximate quantile from bucket counts (upper bound of the
        bucket containing the q-th observation).  Edge behavior: NaN on
        an empty/unknown label set; q clamps to [0, 1]; q=0 returns the
        first non-empty bucket's bound (a single observation answers
        every quantile with its own bucket); +Inf past the last bucket.
        Never raises."""
        k = _label_key(labels)
        with self._lock:
            counts = list(self._counts.get(k, ()))
            total = self._total.get(k, 0)
        if not counts or total <= 0:
            return float("nan")
        q = min(max(q, 0.0), 1.0)
        # At least the first observation: q=0 must land in a non-empty
        # bucket, not the (possibly empty) first one.
        target = max(1, math.ceil(q * total))
        acc = 0
        for i, c in enumerate(counts):
            acc += c
            if acc >= target:
                return self.buckets[i] if i < len(self.buckets) else float("inf")
        return float("inf")

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        with self._lock:
            # Snapshot under the lock: a concurrent observe() between
            # reading _counts and _sum would emit torn cumulative counts
            # (bucket cum > _count, or _sum missing the observation).
            snap = {k: (list(self._counts[k]), self._sum[k])
                    for k in self._counts}
        for k in sorted(snap):
            counts, total_sum = snap[k]
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += counts[i]
                le = _fmt_labels(k, 'le="%s"' % b)
                out.append(f"{self.name}_bucket{le} {cum}")
            cum += counts[-1]
            le_inf = _fmt_labels(k, 'le="+Inf"')
            out.append(f"{self.name}_bucket{le_inf} {cum}")
            out.append(f"{self.name}_sum{_fmt_labels(k)} {total_sum}")
            out.append(f"{self.name}_count{_fmt_labels(k)} {cum}")
        return out


# What the engine thread can be doing, one at a time (EngineStepCounters.
# enter).  Flat on purpose: a device capture shows each as one host event
# `engine.<phase>`, and a reader that labels an idle gap with the single
# event of largest overlap would lose every phase under an enclosing span.
ENGINE_PHASES = (
    "idle",              # step loop waiting for work (InferenceEngine)
    "commands",          # draining adds, cancels and calls
    "settle_first",      # collecting async first tokens, host part
    "plan",              # mixed budget, scheduler.plan(), window cohort
    "dispatch_window",   # host inputs, uploads, enqueue of a decode window
    "dispatch_prefill",  # packing and enqueue of a prefill batch
    "wait_device",       # every blocking device->host read (= host_syncs)
    "emit",              # the token loop after a sync
    "single_step",       # single-step / speculative decode, host part
    "deliver",           # hand-off to the asyncio loop, dead requests, gauges
    "dispatch_block",    # host inputs and enqueue of a block-diffusion step
)
(PHASE_IDLE, PHASE_COMMANDS, PHASE_SETTLE_FIRST, PHASE_PLAN,
 PHASE_DISPATCH_WINDOW, PHASE_DISPATCH_PREFILL, PHASE_WAIT_DEVICE,
 PHASE_EMIT, PHASE_SINGLE_STEP, PHASE_DELIVER,
 PHASE_DISPATCH_BLOCK) = range(len(ENGINE_PHASES))
_PHASE_SPAN_NAMES = tuple("engine." + p for p in ENGINE_PHASES)

# Where a live request of an engine is, exactly one at a time, from
# `Scheduler.add_request` to `Scheduler.finish` (EngineStepCounters.
# request_state, the request-state clock).  The phase clock above says
# what the engine THREAD does; this one says what each REQUEST waits for.
REQUEST_STATES = (
    "waiting",       # in scheduler.waiting: no slot, no pages, or held by QoS
    "budget_wait",   # admitted (slot and pages held), no chunk planned yet
    "prefill",       # from its first planned chunk to its last chunk done
    "first_token",   # prefill done, its first token not yet appended
    "cohort_wait",   # first token out, no decode dispatch holds its row yet
    "decode",        # from the first decode dispatch that holds its row
    "preempted",     # from a preemption to its next decode dispatch
)
(RS_WAITING, RS_BUDGET_WAIT, RS_PREFILL, RS_FIRST_TOKEN, RS_COHORT_WAIT,
 RS_DECODE, RS_PREEMPTED) = range(len(REQUEST_STATES))
RS_NONE = -1         # not on the clock: before add_request, after finish
# Why the head of `scheduler.waiting` was not admitted, as `_try_admit`
# last left its loop with requests still queued.
ADMIT_BLOCKED = ("slots", "pages", "held")
BLOCKED_SLOTS, BLOCKED_PAGES, BLOCKED_HELD = range(len(ADMIT_BLOCKED))
# What became of an engine iteration's one chance to dispatch a prefill
# chunk (EngineCore._end_step).
PREFILL_CHANCES = ("dispatched", "duty_skipped", "no_budget", "no_window")
(CHANCE_DISPATCHED, CHANCE_DUTY_SKIPPED, CHANCE_NO_BUDGET,
 CHANCE_NO_WINDOW) = range(len(PREFILL_CHANCES))
# Where a row joined a window cohort (EngineCore._mark_decode): at the
# first decode dispatch after the chunk that completed its prompt, or
# after dispatches of the old cohort (at the settle of a batched merge).
COHORT_JOINS = ("chunk", "settle")
JOIN_AT_CHUNK, JOIN_AT_SETTLE = range(len(COHORT_JOINS))


class EngineStepCounters:
    """Serving-loop overhead counters the engine increments in-line.

    The r5→r6 diagnosis needed exactly these and had none: the serving
    number halved and nothing could say whether the loss was host syncs,
    recompiles, or scheduler churn.  Counted on the engine thread only
    (no locking), cheap enough for the per-step hot path:

    - `host_syncs` — blocking device→host reads the step loop performed
      (window token fetches, single-step sample fetches, blocking
      first-token settles).  Steady-state window decode must pay at most
      ONE per window; anything above that is a pipeline bug.
    - `xla_cache_misses` — first-seen (program, shape-signature) pairs
      via `note_dispatch`.  jax's jit cache keys on exactly these, so a
      nonzero delta after warmup means the engine is churning shapes
      (bucket flapping) and recompiling.  It is a proxy: it counts what
      WOULD miss jax's in-process cache, including hits served by the
      persistent compilation cache on disk.
    - dispatch tallies (`window_dispatches`, `single_step_dispatches`,
      `prefill_dispatches`, `spec_dispatches`, `h2d_uploads`) —
      denominators for the two above (syncs *per window*, uploads *per
      dispatch*).
    - `kv_read_bytes_modeled` / `decode_tokens_emitted` (via
      `note_kv_read`) — the MODELED KV bytes decode attention swept from
      HBM and the tokens those sweeps emitted.  Their ratio,
      `effective_bytes_per_token`, is the decode-bandwidth-wall series
      (ISSUE 6): int8 KV roughly halves the numerator, speculative
      decoding grows the denominator per sweep — both show up here
      without a TPU in the loop.  Under a mesh the bytes are PER CHIP
      (the engine divides by its `kv_traffic_shards` = dp*tp on non-pp
      meshes, pp on pipelines — ISSUE 9): a tp2 engine sweeps half the
      cache bytes per chip, a dp2 engine half the ROWS per chip, and the
      per-chip mbu derived from this series must say so.  (Residency
      gauges divide by the distinct `kv_shard_count` — plain dp
      replicates storage while halving traffic.)
    - `prefill_tokens_dispatched` — prompt tokens handed to a prefill
      program (packed or padded), counted where `prefill_dispatches` is:
      the admitted prompt tokens less what the prefix cache skipped.
    - the BLOCK-DIFFUSION tallies (`note_block_step`, `note_moe`;
      `block_metrics_lines` for `/metrics`): forwards by kind (denoising,
      commit) and those of them that ran past their last K/V write (the
      program's own count: a served commit stops there), blocks
      committed, positions unmasked, live rows times
      forwards (the denominator of tokens a row a forward), and for the
      routed-expert layers the (token, expert) assignments computed and
      the distinct experts that got at least one row, summed over layers
      and forwards (prefill chunks, decode windows and single steps
      included; `moe_layer_forwards` counts expert layers, so a leading
      dense layer is not among them; `moe_packed_rows` the rows of the
      grouped kernel's packed buffers, a static shape reckoned on the
      host), and `prefill_attn_pairs`
      (`note_prefill_pairs`).  A block program call
      counts as one `window_dispatches`: it stands where the decode
      window stands.  Not in `to_dict()`: a causal engine never moves
      them.
    - the PHASE CLOCK (`enter`, `phase_ns`, `phase_entries`): where the
      engine thread's wall time goes, one phase of `ENGINE_PHASES` at a
      time.  Two sinks: `phase_seconds()` for `/metrics`, and — only
      while `trace_phases` is set by `DeviceProfiler.capture` — one
      `jax.profiler.TraceAnnotation("engine.<phase>")` per phase, so the
      phases are events of the device capture, on its clock.  A clock,
      so NOT in `to_dict()` (see the EWMAs below for why).
    - the REQUEST-STATE CLOCK (`request_state`, `req_state_n`,
      `req_state_ns`, `req_state_entries`): where each live request's
      seconds go between `add_request` and its last token, one state of
      `REQUEST_STATES` at a time.  `req_state_n[s]` is the number of
      requests now in state s and `req_state_ns[s]` the integral of that
      count over time, so between any two scrapes the seconds of all
      states sum to the integral of live requests, whatever the scrapes
      cut through: a wait is charged to the seconds in which it was
      waited, not to the scrape in which its request ended.  Every
      transition happens on the engine thread (scheduler and core), reads
      `perf_counter_ns` once (the phase clock's clock: one timeline) and
      costs a few integer operations; nothing per token, nothing per
      window for a row that stays in its cohort.  A state passed through
      in no time (a fully cached prompt) is entered and left at one clock
      reading: the entry counts, the seconds do not.  A block-diffusion
      engine samples no token from prefill: there a request is in
      `decode` from the first block call that holds its row (its first
      token comes with that call's read, which `first_token` on the
      ledger runs to) and never in `cohort_wait`.  On the same clock:
      `admit_blocked_ns` (why the head of the queue is not admitted,
      `set_admit_blocked`).  Beside it, plain tallies: `prefill_chances`
      (what became of each iteration's chance to dispatch a chunk),
      `cohort_joins` (rows a window cohort took in, by whether a decode
      dispatch of the old cohort went out after their last chunk),
      `request_first_tokens`, `request_output_tokens`.  Always on, in no
      tracer's or ledger's guard, and NOT in `to_dict()`.
    """

    def __init__(self) -> None:
        self.phase_ns = [0] * len(ENGINE_PHASES)
        self.phase_entries = [0] * len(ENGINE_PHASES)
        self._phase = PHASE_IDLE
        self._phase_t0 = self._phase_settled = time.perf_counter_ns()
        self.trace_phases = False
        self._phase_span = None
        n_states = len(REQUEST_STATES)
        self.req_state_n = [0] * n_states
        self.req_state_ns = [0] * n_states
        self.req_state_entries = [0] * n_states
        self._req_state_t0 = self._phase_t0
        self._req_state_seq = 0          # odd: a transition is under way
        self.admit_blocked_ns = [0] * len(ADMIT_BLOCKED)
        self._admit_blocked = RS_NONE
        self.prefill_chances = [0] * len(PREFILL_CHANCES)
        self.cohort_joins = [0] * len(COHORT_JOINS)
        self.request_first_tokens = 0
        self.request_output_tokens = 0
        self.prefill_tokens_dispatched = 0
        self.host_syncs = 0
        self.xla_cache_misses = 0
        self.window_dispatches = 0
        self.window_syncs = 0
        self.single_step_dispatches = 0
        self.prefill_dispatches = 0
        self.packed_prefill_dispatches = 0
        self.spec_dispatches = 0
        self.h2d_uploads = 0
        self.kv_read_bytes_modeled = 0
        self.decode_tokens_emitted = 0
        self.diffusion_denoise_forwards = 0
        self.diffusion_commit_forwards = 0
        self.diffusion_scored_forwards = 0
        self.diffusion_blocks_committed = 0
        self.diffusion_positions_unmasked = 0
        self.diffusion_row_forwards = 0
        self.diffusion_experts_touched = 0
        # The block path reads one call behind: calls dispatched while the
        # one before was unread, and rows a call computed whose block was
        # dropped at the read (the sequence had ended meanwhile).
        self.block_calls_overlapped = 0
        self.diffusion_rows_dropped = 0
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        self.moe_layer_forwards = 0
        self.moe_packed_rows = 0
        self.moe_decode_experts_touched = 0
        self.moe_decode_layer_forwards = 0
        # A model that holds a share of its experts (`experts_held`): of
        # `moe_assignments` (what the router chose over all experts) those
        # whose expert is held here; `moe_experts_touched` then counts held
        # experts.  The `moe_capture_*` six tally the calls dispatched while
        # a device capture runs, decode and prefill apart (`note_moe_capture`),
        # as the `ssm_capture_*` four do.
        self.moe_local_assignments = 0
        self.moe_capture = {
            at: {"local_assignments": 0, "experts_touched": 0,
                 "layer_forwards": 0} for at in ("decode", "prefill")}
        # Layers by kind, for a model whose layers differ by a pattern
        # ({"ssm": n, "attention": n, "moe": n}; empty otherwise).
        self.model_layers: Dict[str, int] = {}
        # Causal (query, context) token pairs the prefill chunks
        # dispatched: the prefill attention kernel's work.
        self.prefill_attn_pairs = 0
        # A model with window layers (`attn_window` > 0: the window's
        # length; `attn_kind_layers` its layers by kind).  `attn_pairs`:
        # (query, key) pairs the attention visits, a layer each, by where
        # ("decode": a row of a step, "prefill": a chunk) and by layer kind,
        # a window layer's counted at min(context, window); "unwindowed" is
        # what a model of this depth without a window would visit, "queries"
        # the queries themselves (a decode row a step, a prompt token).  The
        # `attn_capture_*` tallies hold the same, and the decode steps and
        # prefill calls it came in, for the calls dispatched while a device
        # capture runs (`trace_phases`), as the `ssm_capture_*` four do.
        # Beside them the window group's pool (the existing pool series
        # keep meaning the full group) and the blocks it got back from
        # behind a window.
        self.attn_window = 0
        self.attn_kind_layers = {"window": 0, "full": 0}
        kinds = ("window", "full", "unwindowed", "queries")
        self.attn_pairs = {at: dict.fromkeys(kinds, 0)
                           for at in ("decode", "prefill")}
        self.attn_capture_pairs = {at: dict.fromkeys(kinds, 0)
                                   for at in ("decode", "prefill")}
        self.attn_capture_calls = {"decode": 0, "prefill": 0}
        self.window_pool = {"capacity": 0, "used": 0, "full_used": 0}
        self.window_blocks_released = 0
        # A model with state-space layers (`note_ssm_decode`,
        # `note_ssm_prefill`; all host ints reckoned at the dispatch): live
        # rows x steps of the decode calls (a window of K steps over R rows
        # adds R * K; each is one state slot read and written a layer) and
        # the rows x steps of those calls' buckets (1 - live / bucket is the
        # share of rows the update kernel moves no state for),
        # prompt tokens scanned and segments (one slot read and written a
        # layer each) of the prefill chunks; the slots in use and in all,
        # and the bytes of one.  The `ssm_capture_*` four tally the same
        # work, and the decode steps and prefill calls it came in, for the
        # calls dispatched while a device capture runs (`trace_phases`): a
        # trace's device time is divided by the work of its own seconds,
        # not by that of the capture's scrapes, which lie the profile's
        # collection apart (27 s around a 3 s trace) while the rows move.
        self.ssm_decode_row_steps = 0
        self.ssm_decode_bucket_row_steps = 0
        self.ssm_prefill_tokens = 0
        self.ssm_prefill_segments = 0
        self.ssm_capture_decode_row_steps = 0
        self.ssm_capture_decode_steps = 0
        self.ssm_capture_prefill_tokens = 0
        self.ssm_capture_prefill_calls = 0
        self.ssm_slots_used = 0
        self.ssm_slots_capacity = 0
        self.ssm_state_bytes_per_slot = 0
        # Modeled PER-CHIP ICI bytes the ring-SP prefill exchange moved
        # (ISSUE 12 satellite): each chip sends its resident K/V chunk on
        # (sp−1) of sp hops per layer, so the series halves when the
        # quantized cache halves the per-token ring payload
        # (KvCacheConfig.ring_payload_bytes_per_token) — the sp analog of
        # the kv_read_bytes_modeled honesty series.
        self.ring_exchange_bytes_modeled = 0
        # Prefills whose ring exchange ran the Pallas flash kernel
        # (ops/pallas/ring_attention.py) rather than the XLA ppermute
        # ring.  The byte series above is PATH-INDEPENDENT (both rings
        # move the same rows+scales over the same sp-1 hops — charged
        # before the dispatch split); this counter is the attribution:
        # tests/test_compose_matrix.py's sp2 cells assert it went up
        # exactly where the kernel was asked for.
        self.ring_kernel_prefills = 0
        # What the rule of mixed prefill (`EngineCore._chunk_rides`) goes
        # by, in engine-thread wall seconds: `window_s`, an EWMA of the
        # sync interval of a plain window, and `chunk_s`, for each chunk
        # token bucket seen, an EWMA of the excess over `window_s` of an
        # interval that had such a chunk behind it.  Fed by
        # note_window_interval at the window sync, the engine's ONE
        # existing blocking point, so it costs no sync.  Beside them the
        # sums the share gauge is made of.  NOT in to_dict(): delta-pinned
        # counter tests compare exact ints.  None / absent = no sample yet.
        self.window_s: Optional[float] = None
        self.chunk_s: Dict[int, float] = {}
        self.chunk_seconds = 0.0
        self.interval_seconds = 0.0
        self._seen_shapes: set = set()
        # Optional first-seen-shape hook (the engine points this at its
        # flight recorder so every recompile leaves a postmortem event);
        # called ONLY on cache misses, so the steady window never pays
        # for it.
        self.on_recompile: Optional[Callable] = None

    @hot_path
    def enter(self, phase: int) -> None:
        """The engine thread starts doing `phase`; whatever it was doing
        ends here.  Entering the phase it is in changes nothing on the
        clock (an idle loop that wakes to find no work stays one `idle`
        event); only the annotation sink may have to move, when a capture
        began or ended while the thread stayed in one phase.  One clock
        read and two integer adds; outside a device capture the sink
        costs the attribute reads below.

        `_phase_t0` is a transition's first store and `_phase_settled`
        its last: `phase_seconds()` reads them in the opposite order and
        takes only a copy between which no transition began."""
        if phase == self._phase:
            if self.trace_phases != (self._phase_span is not None):
                self._trace_phase(phase)
            return
        now = time.perf_counter_ns()
        t0 = self._phase_t0
        self._phase_t0 = now
        self.phase_ns[self._phase] += now - t0
        self.phase_entries[phase] += 1
        self._phase = phase
        self._phase_settled = now
        if self.trace_phases or self._phase_span is not None:
            self._trace_phase(phase)

    def _trace_phase(self, phase: int) -> None:
        """Sink 2: close the open `engine.<phase>` event and, while the
        capture still runs, open the next.  Engine thread only — a TraceMe
        ends on the thread that began it."""
        span, self._phase_span = self._phase_span, None
        if span is not None:
            span.__exit__(None, None, None)
        if self.trace_phases:
            from jax.profiler import TraceAnnotation

            span = TraceAnnotation(_PHASE_SPAN_NAMES[phase])
            span.__enter__()
            self._phase_span = span

    @property
    def phase_event_open(self) -> bool:
        """Whether sink 2 holds an open event (`DeviceProfiler.capture`
        waits for it to close before it stops the trace)."""
        return self._phase_span is not None

    def restart_phase_clock(self, phase: int = PHASE_IDLE) -> None:
        """A new thread takes the core (InferenceEngine's step loop after
        the constructing thread's warm-up): the time nobody drove it is
        no phase's."""
        self._phase_t0 = self._phase_settled = time.perf_counter_ns()
        self._phase = phase

    def phase_seconds(self) -> Dict[str, float]:
        """Seconds per phase so far, the open phase's elapsed part
        included, so the values sum to the wall time since the clock
        (re)started at whatever instant they are read.  Safe from any
        thread but the engine's: a copy during which the engine thread
        was inside a transition is taken again, once it has had the
        interpreter to finish it (see `enter` for the order of its
        stores)."""
        for _ in range(64):
            settled = self._phase_settled
            phase, ns = self._phase, list(self.phase_ns)
            now = time.perf_counter_ns()
            if self._phase_t0 == settled:
                break
            time.sleep(0.0005)
        ns[phase] += max(0, now - settled)
        return {name: v / 1e9 for name, v in zip(ENGINE_PHASES, ns)}

    def phase_metrics_lines(self) -> List[str]:
        """Sink 1: the phase clock as Prometheus text for the worker's
        `/metrics`, beside the `dynamo_worker_engine_*` counters."""
        secs = self.phase_seconds()
        return [
            *(f'dynamo_worker_engine_phase_seconds_total{{phase="{p}"}} '
              f'{secs[p]:.6f}' for p in ENGINE_PHASES),
            *(f'dynamo_worker_engine_phase_entries_total{{phase="{p}"}} {n}'
              for p, n in zip(ENGINE_PHASES, self.phase_entries)),
        ]

    # -- the request-state clock -------------------------------------------

    def _settle_request_clock(self, now: int) -> None:
        """Open a transition and charge the time since the last one to the
        states the live requests are in (and to the standing admission
        block).  `_req_state_seq` goes odd here, first, and even again as
        the caller's last store: `request_state_seconds()` keeps only a
        copy taken under one even value (`phase_seconds()`'s guard, with a
        count where that compares two clock readings, since transitions
        of one instant share theirs)."""
        self._req_state_seq += 1
        dt = now - self._req_state_t0
        self._req_state_t0 = now
        if dt:
            ns = self.req_state_ns
            for s, n in enumerate(self.req_state_n):
                if n:
                    ns[s] += n * dt
            if self._admit_blocked >= 0:
                self.admit_blocked_ns[self._admit_blocked] += dt

    def request_state(self, req, state: int, now: int = 0) -> int:
        """`req` (a scheduler `Request`) moves to `state` (`RS_NONE`: it
        leaves the clock); returns the clock reading, which a caller with
        several transitions at one instant hands to the next as `now`.
        The request keeps the reading as its entry into the state and the
        nanoseconds it spent in the one it leaves."""
        old = req.clock_state
        if old == state:
            return now or time.perf_counter_ns()
        if not now:
            now = time.perf_counter_ns()
        self._settle_request_clock(now)
        n = self.req_state_n
        if old >= 0:
            n[old] -= 1
            req.state_ns[old] += now - req.state_entry_ns[old]
        req.clock_state = state
        if state >= 0:
            n[state] += 1
            self.req_state_entries[state] += 1
            req.state_entry_ns[state] = now
        self._req_state_seq += 1
        return now

    def set_admit_blocked(self, reason: int) -> None:
        """The standing reason the head of the queue is not admitted
        (`RS_NONE`: nothing waits, or nothing blocks).  Reads the clock
        only when the reason changes."""
        if reason != self._admit_blocked:
            now = time.perf_counter_ns()
            self._settle_request_clock(now)
            self._admit_blocked = reason
            self._req_state_seq += 1

    def request_state_seconds(self):
        """({state: seconds}, {reason: seconds}) so far, the open part
        (`req_state_n[s]` x the time since the last transition) included.
        Safe from any thread but the engine's: a copy during which a
        transition was under way is taken again."""
        for _ in range(64):
            seq = self._req_state_seq
            n, ns = list(self.req_state_n), list(self.req_state_ns)
            blocked, b_ns = self._admit_blocked, list(self.admit_blocked_ns)
            t0, now = self._req_state_t0, time.perf_counter_ns()
            if not seq & 1 and self._req_state_seq == seq:
                break
            time.sleep(0.0005)
        dt = max(0, now - t0)
        if blocked >= 0:
            b_ns[blocked] += dt
        return ({name: (ns[s] + n[s] * dt) / 1e9
                 for s, name in enumerate(REQUEST_STATES)},
                {name: v / 1e9 for name, v in zip(ADMIT_BLOCKED, b_ns)})

    def request_state_metrics_lines(self) -> List[str]:
        """The request-state clock and its tallies as Prometheus text for
        the worker's `/metrics`, beside `phase_metrics_lines()`."""
        states, blocked = self.request_state_seconds()
        w = "dynamo_worker_"
        return [
            *(f'{w}request_state_seconds_total{{state="{s}"}} '
              f'{states[s]:.6f}' for s in REQUEST_STATES),
            *(f'{w}request_state_entries_total{{state="{s}"}} {n}'
              for s, n in zip(REQUEST_STATES, self.req_state_entries)),
            f"{w}request_first_tokens_total {self.request_first_tokens}",
            f"{w}request_output_tokens_total {self.request_output_tokens}",
            *(f'{w}admit_blocked_seconds_total{{reason="{r}"}} '
              f'{blocked[r]:.6f}' for r in ADMIT_BLOCKED),
            *(f'{w}prefill_chances_total{{outcome="{o}"}} {n}'
              for o, n in zip(PREFILL_CHANCES, self.prefill_chances)),
            *(f'{w}cohort_joins_total{{at="{a}"}} {n}'
              for a, n in zip(COHORT_JOINS, self.cohort_joins)),
            # What the rule of mixed prefill bounds: the measured share
            # of the window path's seconds that chunks took.
            f"{w}prefill_chunk_seconds_share "
            f"{self.chunk_seconds / (self.interval_seconds or 1.0):.6f}",
        ]

    @property
    def decode_dispatches(self) -> int:
        """Decode programs dispatched, whatever the path: windows (and
        block calls), speculative and single steps."""
        return (self.window_dispatches + self.spec_dispatches
                + self.single_step_dispatches)

    def note_dispatch(self, tag: str, *sig) -> bool:
        """Record a jitted-program dispatch; a first-seen (tag, sig)
        counts as an XLA cache miss (a new shape compiles).  Returns
        True exactly on first-seen — the dispatch site uses it to feed
        the device-profiler's compile-time cost-analysis harvest
        without any steady-state branch cost."""
        key = (tag,) + sig
        if key not in self._seen_shapes:
            self._seen_shapes.add(key)
            self.xla_cache_misses += 1
            cb = self.on_recompile
            if cb is not None:
                cb(key)
            return True
        return False

    def note_kv_read(self, nbytes: int, tokens: int) -> None:
        """Tally modeled decode KV traffic (bytes swept) and the tokens
        it emitted; host-int arithmetic only."""
        self.kv_read_bytes_modeled += int(nbytes)
        self.decode_tokens_emitted += int(tokens)

    def note_block_step(self, rows: int, denoise: int, unmasked: int,
                        experts_touched: int = 0, dropped: int = 0,
                        scored: int = 0) -> None:
        """One block program call: `rows` live rows through `denoise`
        denoising forwards and one commit, `unmasked` positions decided,
        `experts_touched` distinct experts with a row summed over the
        call's layers and forwards (also part of `note_moe`'s tally, which
        takes in the prefill chunks as well).  `dropped` of the rows were
        computed for a sequence that had ended by the read: their blocks
        are not committed to any stream.  `scored` of the forwards ran
        past their last K/V write (the last layer's attention read and
        experts, the head, the unmasking), as the program counted them."""
        self.diffusion_experts_touched += int(experts_touched)
        self.diffusion_denoise_forwards += int(denoise)
        self.diffusion_commit_forwards += 1
        self.diffusion_scored_forwards += int(scored)
        self.diffusion_blocks_committed += int(rows) - int(dropped)
        self.diffusion_rows_dropped += int(dropped)
        self.diffusion_positions_unmasked += int(unmasked)
        self.diffusion_row_forwards += int(rows) * (int(denoise) + 1)

    def note_moe(self, assignments: int, touched: int,
                 layer_forwards: int, decode_touched: int = 0,
                 decode_layer_forwards: int = 0,
                 packed_rows: int = 0) -> None:
        """Routed-expert work the device reported: (token, expert) pairs
        computed, distinct experts with at least one row summed over
        `layer_forwards` expert layers run; and, of those two, what the
        causal decode calls (windows, single steps) account for.
        `packed_rows`: rows of the packed buffers the grouped kernel walked
        for those layers, reckoned on the host from the programs' static
        shapes (assignments over it is the buffers' fill)."""
        self.moe_assignments += int(assignments)
        self.moe_packed_rows += int(packed_rows)
        self.moe_experts_touched += int(touched)
        self.moe_layer_forwards += int(layer_forwards)
        self.moe_decode_experts_touched += int(decode_touched)
        self.moe_decode_layer_forwards += int(decode_layer_forwards)

    def note_moe_capture(self, tally, layers) -> None:
        """What the expert layers dispatched inside a device capture
        reported: `tally` [2, 2], rows (decode, prefill), columns
        (assignments of held experts, held experts touched); `layers` the
        expert layers each row covers."""
        for row, at in enumerate(("decode", "prefill")):
            held = self.moe_capture[at]
            held["local_assignments"] += int(tally[row][0])
            held["experts_touched"] += int(tally[row][1])
            held["layer_forwards"] += int(layers[row])

    def note_prefill_pairs(self, items) -> None:
        """The prefill attention's work in one call, reckoned on the host
        from the chunks' lengths: a chunk of n tokens behind s cached ones
        is n * s + n * (n + 1) / 2 causal (query, context) pairs."""
        self.prefill_attn_pairs += sum(
            w.length * w.start + w.length * (w.length + 1) // 2
            for w in items)

    def note_attn_pairs(self, at: str, spans, calls: int = 1) -> None:
        """The attention's work in one call of a model with window layers,
        reckoned on the host: `spans` (first, count) a row or chunk, whose
        queries stand at positions first .. first + count - 1 and each see
        the positions up to their own (a window layer the last
        `attn_window` of them).  `calls`: the steps or calls it came in."""
        w = self.attn_window
        if not w:
            return
        full = win = queries = 0
        for first, n in spans:
            queries += n
            # sum over q in [first, first + n) of (q + 1), of min(q + 1, w)
            full += n * first + n * (n + 1) // 2
            below = min(max(w - 1 - first, 0), n)    # queries with q + 1 < w
            win += below * first + below * (below + 1) // 2 + (n - below) * w
        layers = self.attn_kind_layers
        add = {"window": win * layers["window"],
               "full": full * layers["full"],
               "unwindowed": full * (layers["window"] + layers["full"]),
               "queries": queries}
        sinks = [self.attn_pairs[at]]
        if self.trace_phases:
            sinks.append(self.attn_capture_pairs[at])
            self.attn_capture_calls[at] += int(calls)
        for sink in sinks:
            for kind, n in add.items():
                sink[kind] += n

    def note_ssm_decode(self, rows: int, steps: int, bucket: int) -> None:
        """A decode call of a model with state-space layers: `rows` live
        rows of a program of `bucket` rows through `steps` state updates
        each."""
        self.ssm_decode_row_steps += int(rows) * int(steps)
        self.ssm_decode_bucket_row_steps += int(bucket) * int(steps)
        if self.trace_phases:
            self.ssm_capture_decode_row_steps += int(rows) * int(steps)
            self.ssm_capture_decode_steps += int(steps)

    def note_ssm_prefill(self, items) -> None:
        """A prefill call of such a model: its chunks' tokens through the
        chunked scan, one segment a chunk."""
        tokens = sum(w.length for w in items)
        self.ssm_prefill_tokens += tokens
        self.ssm_prefill_segments += len(items)
        if self.trace_phases:
            self.ssm_capture_prefill_tokens += tokens
            self.ssm_capture_prefill_calls += 1

    def block_metrics_lines(self) -> List[str]:
        """The block-diffusion and routed-expert tallies as Prometheus
        text for the worker's `/metrics`; nothing from an engine that
        never ran a block step or an expert layer."""
        lines = []
        if self.diffusion_commit_forwards:
            lines += [
                'dynamo_worker_diffusion_forwards_total{kind="denoise"} '
                f'{self.diffusion_denoise_forwards}',
                'dynamo_worker_diffusion_forwards_total{kind="commit"} '
                f'{self.diffusion_commit_forwards}',
                'dynamo_worker_diffusion_scored_forwards_total '
                f'{self.diffusion_scored_forwards}',
                'dynamo_worker_diffusion_blocks_committed_total '
                f'{self.diffusion_blocks_committed}',
                'dynamo_worker_diffusion_positions_unmasked_total '
                f'{self.diffusion_positions_unmasked}',
                'dynamo_worker_diffusion_row_forwards_total '
                f'{self.diffusion_row_forwards}',
                'dynamo_worker_diffusion_experts_touched_total '
                f'{self.diffusion_experts_touched}',
                'dynamo_worker_diffusion_rows_dropped_total '
                f'{self.diffusion_rows_dropped}',
                'dynamo_worker_block_calls_overlapped_total '
                f'{self.block_calls_overlapped}',
            ]
        if self.moe_layer_forwards:
            lines += [
                f'dynamo_worker_moe_assignments_total {self.moe_assignments}',
                'dynamo_worker_moe_experts_touched_total '
                f'{self.moe_experts_touched}',
                'dynamo_worker_moe_layer_forwards_total '
                f'{self.moe_layer_forwards}',
                'dynamo_worker_moe_packed_rows_total '
                f'{self.moe_packed_rows}',
            ]
        if self.moe_decode_layer_forwards:
            lines += [
                'dynamo_worker_moe_decode_experts_touched_total '
                f'{self.moe_decode_experts_touched}',
                'dynamo_worker_moe_decode_layer_forwards_total '
                f'{self.moe_decode_layer_forwards}',
            ]
        if self.moe_local_assignments:
            lines += [
                'dynamo_worker_moe_routed_assignments_total '
                f'{self.moe_assignments}',
                'dynamo_worker_moe_local_assignments_total '
                f'{self.moe_local_assignments}']
            lines += [
                f'dynamo_worker_moe_capture_{at}_{what}_total {n}'
                for at, held in self.moe_capture.items()
                for what, n in held.items()]
        lines += [f'dynamo_model_layers{{kind="{kind}"}} {n}'
                  for kind, n in self.model_layers.items()]
        if self.prefill_attn_pairs:
            lines.append('dynamo_worker_prefill_attn_pairs_total '
                         f'{self.prefill_attn_pairs}')
        if self.attn_window:
            lines += [
                f'dynamo_worker_attn_pairs_total{{at="{at}",kind="{kind}"}} '
                f'{n}' for at, kinds in self.attn_pairs.items()
                for kind, n in kinds.items()]
            lines += [
                f'dynamo_worker_attn_capture_pairs_total{{at="{at}",'
                f'kind="{kind}"}} {n}'
                for at, kinds in self.attn_capture_pairs.items()
                for kind, n in kinds.items()]
            lines += [
                f'dynamo_worker_attn_capture_calls_total{{at="{at}"}} {n}'
                for at, n in self.attn_capture_calls.items()]
            lines += [
                f'dynamo_kv_window_pool_blocks{{state="{state}"}} {n}'
                for state, n in self.window_pool.items()]
            lines += [
                f'dynamo_attn_window_tokens {self.attn_window}',
                'dynamo_kv_window_blocks_released_total '
                f'{self.window_blocks_released}']
        if self.ssm_slots_capacity:
            lines += [
                'dynamo_worker_ssm_decode_row_steps_total '
                f'{self.ssm_decode_row_steps}',
                'dynamo_worker_ssm_decode_bucket_row_steps_total '
                f'{self.ssm_decode_bucket_row_steps}',
                'dynamo_worker_ssm_prefill_tokens_total '
                f'{self.ssm_prefill_tokens}',
                'dynamo_worker_ssm_prefill_segments_total '
                f'{self.ssm_prefill_segments}',
                'dynamo_worker_ssm_capture_decode_row_steps_total '
                f'{self.ssm_capture_decode_row_steps}',
                'dynamo_worker_ssm_capture_decode_steps_total '
                f'{self.ssm_capture_decode_steps}',
                'dynamo_worker_ssm_capture_prefill_tokens_total '
                f'{self.ssm_capture_prefill_tokens}',
                'dynamo_worker_ssm_capture_prefill_calls_total '
                f'{self.ssm_capture_prefill_calls}',
                'dynamo_ssm_state_slots{state="used"} '
                f'{self.ssm_slots_used}',
                'dynamo_ssm_state_slots{state="capacity"} '
                f'{self.ssm_slots_capacity}',
                'dynamo_ssm_state_bytes_per_slot '
                f'{self.ssm_state_bytes_per_slot}',
            ]
        return lines

    def note_ring_exchange(self, nbytes: int) -> None:
        """Tally modeled per-chip ring-SP exchange bytes (sp prefill
        dispatches only); host-int arithmetic only."""
        self.ring_exchange_bytes_modeled += int(nbytes)

    def note_window_interval(self, wall_s: float, chunk: int) -> None:
        """Wall time between two consecutive window syncs of a full
        pipeline, which tracks what the device ran between the two
        windows' ends: the second window, and before it the prefill chunk
        of token bucket `chunk` (0: none).  Plain intervals feed
        `window_s`; one with a chunk feeds `chunk_s[chunk]` with its
        excess over `window_s`."""
        if wall_s <= 0 or (chunk and self.window_s is None):
            return

        def ewma(seen, x):
            return x if seen is None else 0.75 * seen + 0.25 * x

        if not chunk:
            # A window's device time does not double from one window to
            # the next; an interval that did held a stall of the host (a
            # dispatch that blocked), which is no window's seconds and
            # would be paid out to chunks as credit.
            self.window_s = ewma(self.window_s,
                                 min(wall_s, 2.0 * (self.window_s or wall_s)))
        else:
            excess = max(wall_s - self.window_s, 0.0)
            self.chunk_s[chunk] = ewma(self.chunk_s.get(chunk), excess)
            self.chunk_seconds += excess
        self.interval_seconds += wall_s

    @property
    def effective_bytes_per_token(self) -> float:
        """Modeled KV HBM bytes per emitted decode token (0 before any
        decode work)."""
        if not self.decode_tokens_emitted:
            return 0.0
        return self.kv_read_bytes_modeled / self.decode_tokens_emitted

    def to_dict(self) -> Dict[str, int]:
        return {
            "host_syncs": self.host_syncs,
            "xla_cache_misses": self.xla_cache_misses,
            "window_dispatches": self.window_dispatches,
            "window_syncs": self.window_syncs,
            "single_step_dispatches": self.single_step_dispatches,
            "prefill_dispatches": self.prefill_dispatches,
            "packed_prefill_dispatches": self.packed_prefill_dispatches,
            "prefill_tokens_dispatched": self.prefill_tokens_dispatched,
            "spec_dispatches": self.spec_dispatches,
            "h2d_uploads": self.h2d_uploads,
            "kv_read_bytes_modeled": self.kv_read_bytes_modeled,
            "decode_tokens_emitted": self.decode_tokens_emitted,
            "ring_exchange_bytes_modeled": self.ring_exchange_bytes_modeled,
            "ring_kernel_prefills": self.ring_kernel_prefills,
        }

    def snapshot(self) -> "EngineStepCounters":
        """Point-in-time copy (delta assertions across a step range)."""
        c = EngineStepCounters()
        c.__dict__.update({k: v for k, v in self.__dict__.items()
                           if k not in ("_seen_shapes", "_phase_span",
                                        "trace_phases")})
        c._seen_shapes = set()
        c.chunk_s = dict(self.chunk_s)
        for name in ("phase_ns", "phase_entries", "req_state_n",
                     "req_state_ns", "req_state_entries",
                     "admit_blocked_ns", "prefill_chances",
                     "cohort_joins"):
            setattr(c, name, list(getattr(self, name)))
        return c

    def delta(self, since: "EngineStepCounters") -> Dict[str, int]:
        now, then = self.to_dict(), since.to_dict()
        return {k: now[k] - then[k] for k in now}


class MetricsRegistry:
    """Named registry with hierarchical prefixes (reference
    `MetricsRegistry`, `lib/runtime/src/metrics.rs`)."""

    def __init__(self, prefix: str = "dynamo") -> None:
        self.prefix = prefix
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name: str, help_: str, **kw):
        full = f"{self.prefix}_{name}" if self.prefix else name
        with self._lock:
            m = self._metrics.get(full)
            if m is None:
                m = cls(full, help_, **kw)
                self._metrics[full] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric {full} already registered as {type(m)}")
            return m

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(Counter, name, help_)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(Gauge, name, help_)

    def histogram(self, name: str, help_: str = "",
                  buckets: Sequence[float] = LATENCY_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help_, buckets=buckets)

    def expose(self) -> str:
        lines: List[str] = []
        with self._lock:
            for m in self._metrics.values():
                lines.extend(m.expose())
        return "\n".join(lines) + "\n"


class RequestMetrics:
    """Per-request lifecycle histograms (`dynamo_request_*`): the series
    the distributed-tracing work surfaces on every process that touches a
    request — frontend `/metrics` observes TTFT / TPOT,
    disagg decode workers observe KV-transfer time.  Distinct from
    FrontendMetrics (whose exact series names the SLA planner's queries
    key on): these are the triage-oriented family `/debug/traces`
    complements."""

    def __init__(self, registry: MetricsRegistry) -> None:
        self.ttft = registry.histogram(
            "request_ttft_seconds", "Request time to first token")
        self.tpot = registry.histogram(
            "request_tpot_seconds", "Per-output-token interval "
            "(time per output token after the first)")
        self.kv_transfer = registry.histogram(
            "request_kv_transfer_seconds",
            "Disaggregated KV-block onboard time (remote prefill pull)")
        self.kv_transfer_overlap = registry.histogram(
            "kv_transfer_overlap",
            "Fraction of the disagg KV prefix streamed before "
            "prefill-done (eager-streaming overlap ratio, 0-1)",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
        # status="ok"|"error" — the SLO monitor's error-rate objective
        # source (runtime/slo.py), observed where the stream finishes
        # (frontend token stream; worker engine_wire_handler).
        self.outcomes = registry.counter(
            "request_outcomes_total",
            "Finished requests by terminal status (ok|error)")

    def observe_outcome(self, ok: bool) -> None:
        self.outcomes.inc(labels={"status": "ok" if ok else "error"})


class FrontendMetrics:
    """The HTTP-service metric family the SLA planner consumes (reference
    `http/service/metrics.rs:61-65,139-142`)."""

    def __init__(self, registry: MetricsRegistry) -> None:
        self.requests_total = registry.counter(
            "frontend_requests_total", "Requests received")
        self.requests_in_flight = registry.gauge(
            "frontend_inflight_requests", "Requests currently being served")
        self.queued_requests = registry.gauge(
            "frontend_queued_requests", "Requests queued before engine entry")
        self.ttft = registry.histogram(
            "frontend_time_to_first_token_seconds", "Time to first token")
        self.itl = registry.histogram(
            "frontend_inter_token_latency_seconds", "Inter-token latency")
        self.request_duration = registry.histogram(
            "frontend_request_duration_seconds", "Full request duration")
        self.input_tokens = registry.histogram(
            "frontend_input_sequence_tokens", "Prompt tokens per request",
            buckets=(16, 64, 256, 1024, 4096, 16384, 65536))
        self.output_tokens = registry.histogram(
            "frontend_output_sequence_tokens", "Output tokens per request",
            buckets=(1, 4, 16, 64, 256, 1024, 4096))


class KvCacheMetrics:
    """Memory-plane telemetry: the capacity-side series KVCache-centric
    schedulers and SLO-driven autoscalers treat as first-class inputs.

    Series (labels `tier` = device|host|disk, `pool` = pool name):

    - `dynamo_kv_pool_{capacity,active,reusable,free}_blocks` — gauges
      sampled from `BlockPool` occupancy views;
    - `dynamo_kv_evictions_total` — LRU evictions per pool;
    - `dynamo_kv_prefix_cache_{hits,misses}_tokens` — prompt tokens
      served from / missed by the prefix cache at admission;
    - `dynamo_hbm_{used,limit}_bytes` (labels `device`, `kind`) —
      per-accelerator HBM occupancy, fed by `HbmPoller`.

    Pull-based: `observe_*` SAMPLES host-side integers the pools and
    scheduler already maintain — called at scrape/pump time off the
    engine thread, so the steady decode window pays zero added host
    syncs and zero dispatches for the telemetry existing (pinned by
    tests/test_kv_metrics.py)."""

    def __init__(self, registry: MetricsRegistry) -> None:
        self.pool_capacity = registry.gauge(
            "kv_pool_capacity_blocks", "KV pool slot capacity")
        self.pool_active = registry.gauge(
            "kv_pool_active_blocks", "KV slots pinned by live sequences")
        self.pool_reusable = registry.gauge(
            "kv_pool_reusable_blocks",
            "Allocatable slots (free + evictable inactive)")
        self.pool_free = registry.gauge(
            "kv_pool_free_blocks", "Slots on the free list")
        self.evictions = registry.counter(
            "kv_evictions_total", "Registered blocks LRU-evicted")
        self.prefix_hits = registry.counter(
            "kv_prefix_cache_hits_tokens",
            "Prompt tokens served from the prefix cache at admission")
        self.prefix_misses = registry.counter(
            "kv_prefix_cache_misses_tokens",
            "Prompt tokens that missed the prefix cache at admission")
        # Fleet-wide prefix reuse (block_manager/prefix_share.py):
        # peer-to-peer prefix pulls driven by router remote-prefix hints.
        self.prefix_remote_hits = registry.counter(
            "prefix_remote_hits_total",
            "Requests whose prefix was pulled from a peer worker")
        self.prefix_remote_pulled = registry.counter(
            "prefix_remote_pulled_blocks_total",
            "KV blocks injected from peer workers via prefix-share pulls")
        self.prefix_remote_fallbacks = registry.counter(
            "prefix_remote_fallbacks_total",
            "Remote-prefix pulls that failed or were refused "
            "(request fell back to local prefill)")
        # Which data plane bulk KV pulls rode (ISSUE 13): plane=device
        # batches crossed device-to-device (reason names the pull site:
        # eager|prefix|disagg); plane=host names WHY the device plane
        # was not used (no_plane, offer_cap, transport, not_resident,
        # pull_failed, quant_mismatch, ...) — a fleet silently degraded
        # to host staging is visible here and in `dynamo top`'s PLANE
        # column.
        self.transfer_plane_choices = registry.counter(
            "kv_transfer_plane_total",
            "Batched bulk-KV pull rounds by data plane (one increment "
            "per pull round on BOTH planes, so device/host reflects "
            "traffic; reason = pull site for device, fallback cause "
            "for host)")
        self.hbm_used = registry.gauge(
            "hbm_used_bytes", "Accelerator memory in use")
        self.hbm_limit = registry.gauge(
            "hbm_limit_bytes", "Accelerator memory capacity")
        # Decode-bandwidth-wall series (ISSUE 6): KV bytes per block as
        # actually stored (incl. int8 scales), modeled KV bytes swept per
        # emitted token, and the speculative-decoding accept telemetry.
        self.kv_bytes_per_block = registry.gauge(
            "kv_bytes_per_block",
            "True PER-CHIP bytes of one KV block across layers, "
            "including quantization scales in int8 mode and divided by "
            "the mesh's KV shard count on sharded pools")
        self.kv_effective_bytes_per_token = registry.gauge(
            "kv_effective_bytes_per_token",
            "Modeled decode-attention HBM bytes per emitted token, "
            "per chip under meshes")
        self.spec_drafted = registry.counter(
            "spec_decode_drafted_tokens_total",
            "Draft tokens proposed to the batched verify step")
        self.spec_accepted = registry.counter(
            "spec_decode_accepted_tokens_total",
            "Draft tokens the verify step accepted")
        self.spec_acceptance_rate = registry.gauge(
            "spec_decode_acceptance_rate",
            "Cumulative accepted/drafted ratio (0 when spec decode off)")
        # Cumulative-source high-water marks: counters can only inc, so
        # sampled monotonic ints (pool.evictions, scheduler token
        # counters) convert to increments by delta from the last sample.
        self._last: Dict[tuple, float] = {}

    def _inc_to(self, counter: Counter, labels: Dict[str, str],
                cum: float) -> None:
        key = (counter.name, _label_key(labels))
        prev = self._last.get(key, 0.0)
        if cum < prev:
            prev = 0.0  # source restarted (fresh pool/engine)
        if cum > prev:
            counter.inc(cum - prev, labels=labels)
        self._last[key] = cum

    @never_engine_thread
    def observe_prefix_share(self, fetcher) -> None:
        """Sample a PrefixFetcher's cumulative pull accounting into the
        dynamo_prefix_remote_* counters (same pull-style delta
        conversion as the pool counters)."""
        self._inc_to(self.prefix_remote_hits, {}, fetcher.remote_hits)
        self._inc_to(self.prefix_remote_pulled, {}, fetcher.pulled_blocks)
        self._inc_to(self.prefix_remote_fallbacks, {}, fetcher.fallbacks)

    @never_engine_thread
    def observe_transfer_plane(self, counts=None) -> None:
        """Sample the device-transfer plane-choice tallies
        (device_transfer.plane_counts — process-wide host ints) into the
        dynamo_kv_transfer_plane_total counter family.  `counts` may be
        passed explicitly (tests)."""
        if counts is None:
            from dynamo_tpu.llm.block_manager.device_transfer import (
                plane_counts)

            counts = plane_counts()
        for (plane, reason), n in counts.items():
            self._inc_to(self.transfer_plane_choices,
                         {"plane": plane, "reason": reason}, n)

    @never_engine_thread
    def observe_pool(self, pool, tier: str) -> None:
        """Sample one BlockPool's occupancy + eviction counters."""
        labels = {"tier": tier, "pool": pool.name}
        self.pool_capacity.set(pool.capacity, labels=labels)
        self.pool_active.set(pool.active_slots, labels=labels)
        self.pool_reusable.set(pool.reusable_slots, labels=labels)
        self.pool_free.set(pool.free_slots, labels=labels)
        self._inc_to(self.evictions, labels, pool.evictions)

    @never_engine_thread
    def observe_engine(self, core) -> None:
        """Sample an EngineCore's block source (all tiers) and the
        scheduler's admission prefix-match counters.  Reads host-side
        ints only — never device arrays — so it is safe to call from a
        scrape thread while the engine steps (and must never BE the
        engine thread: sampling on the step loop would charge the
        steady window for its own telemetry)."""
        alloc = core.allocator
        manager = getattr(alloc, "manager", None)
        if manager is not None:
            self.observe_pool(manager.device, "device")
            if manager.host is not None:
                self.observe_pool(manager.host, "host")
            if manager.disk is not None:
                self.observe_pool(manager.disk, "disk")
            device_pool = manager.device.name
        else:
            # Plain free-list allocator: no pool object, synthesize the
            # device-tier gauges from its counts (no reuse → active =
            # allocated, reusable = free).
            labels = {"tier": "device", "pool": "plain"}
            cap = alloc.num_blocks - 1
            free = alloc.free_blocks
            self.pool_capacity.set(cap, labels=labels)
            self.pool_active.set(cap - free, labels=labels)
            self.pool_reusable.set(free, labels=labels)
            self.pool_free.set(free, labels=labels)
            device_pool = "plain"
        sched = getattr(core, "scheduler", None)
        if sched is not None:
            labels = {"tier": "device", "pool": device_pool}
            self._inc_to(self.prefix_hits, labels,
                         getattr(sched, "prefix_hit_tokens", 0))
            self._inc_to(self.prefix_misses, labels,
                         getattr(sched, "prefix_miss_tokens", 0))
        cache_cfg = getattr(core, "cache_cfg", None)
        if cache_cfg is not None:
            # Per-CHIP bytes: a tp/dp-sharded pool splits every block
            # over kv_shard_count chips, and the HBM-residency math the
            # planner does against dynamo_hbm_* would double-count a
            # whole-block figure (ISSUE 9 satellite).
            shards = getattr(core, "kv_shard_count", 1)
            self.kv_bytes_per_block.set(
                cache_cfg.bytes_per_block / max(shards, 1),
                labels={"kv_quant": cache_cfg.kv_quant})
        counters = getattr(core, "counters", None)
        if counters is not None:
            self.kv_effective_bytes_per_token.set(
                counters.effective_bytes_per_token)
        stats = getattr(getattr(core, "metrics", None),
                        "spec_decode_stats", None)
        if stats is not None:
            self._inc_to(self.spec_drafted, {}, stats.num_drafts)
            self._inc_to(self.spec_accepted, {}, stats.num_accepted_tokens)
            self.spec_acceptance_rate.set(
                stats.num_accepted_tokens / stats.num_drafts
                if stats.num_drafts else 0.0)


class HbmPoller:
    """Slow-poll thread feeding `dynamo_hbm_{used,limit}_bytes` from
    `jax.local_devices()[i].memory_stats()`.

    Off the engine thread by construction (its own daemon thread), and
    `memory_stats()` is a PJRT host-side query — no device dispatch, no
    sync injected into the step loop.  Only for processes that host an
    engine: the first poll initialises the JAX backend, and on a chip
    host that takes the chip.  A backend that reports no memory stats
    (CPU) leaves the series ABSENT — host RSS is not device memory and
    is never written under these names."""

    def __init__(self, metrics: KvCacheMetrics,
                 interval: float = 10.0) -> None:
        self.metrics = metrics
        self.interval = interval
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._warned_no_stats = False

    @never_engine_thread
    def poll_once(self) -> int:
        """One sample of every local device; returns the number of
        devices that reported memory stats."""
        import jax

        reported = 0
        used_total = limit_total = 0
        for i, dev in enumerate(jax.local_devices()):
            stats = dev.memory_stats()
            if not stats or "bytes_in_use" not in stats:
                continue
            labels = {"device": str(i), "kind": dev.platform}
            self.metrics.hbm_used.set(stats["bytes_in_use"], labels=labels)
            used_total += int(stats["bytes_in_use"])
            limit = stats.get("bytes_limit") or stats.get(
                "bytes_reservable_limit")
            if limit:
                self.metrics.hbm_limit.set(limit, labels=labels)
                limit_total += int(limit)
            reported += 1
        if reported:
            # Flight-recorder HBM sample: one aggregate event per poll —
            # the "was HBM climbing before the death" postmortem series.
            flight_recorder.get_recorder().record(
                "hbm", devices=reported, used_bytes=used_total,
                limit_bytes=limit_total)
        elif not self._warned_no_stats:
            self._warned_no_stats = True
            _logger.warning(
                "HBM poll: the %s backend reports no memory stats; "
                "dynamo_hbm_* series stay absent", jax.default_backend())
        return reported

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name="hbm-poll", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll_once()
            except Exception as e:  # telemetry must never kill the process
                warn_rate_limited(
                    _logger, "hbm_poll", 60.0,
                    "HBM poll failed (series go stale): %s", e)
            self._stop.wait(self.interval)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
