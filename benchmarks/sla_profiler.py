"""SLA profiler + capacity frontier: traffic-mix sweeps → the planner's
profile → the cheapest fleet that holds an SLO.

Role of the reference's `benchmarks/profiler/profile_sla.py` at FLEET
granularity: where `planner/profiler.py` measures one engine on bare
(isl, context, kv) grids, this harness profiles whole serving
CONFIGURATIONS across the feature axes PRs 6-10 shipped —

    (tp mesh, worker count, packed prefill,
     int8 KV quant, speculative decode, disaggregated P/D)

— against diverse traffic mixes drawn from `benchmarks/data_generator`
(prefix-heavy agentic tool-call loops, long-context prefill, bursty
diurnal arrivals), and emits:

(a) the TTFT/TPOT-vs-offered-load frontier per config, folded into the
    exact `profile` dict `planner/sla.py:SlaPlanner` and
    `planner/interpolation.py` consume (the `prefill`/`decode` grids are
    unchanged; everything new rides under a `meta` key the
    interpolators ignore — schema v2, round-trips through
    `load_profile`/`save_profile`);
(b) a capacity model: given an SLO (`--ttft-p99`, `--tpot-p99`) and a
    traffic mix at a required load (`--rps`, or `--users`/`--rph` for
    the million-user form), name the cheapest fleet — config + replica
    count — that holds it, or REFUSE when no profiled config can.

Two measurement backends share the sweep:

- **Mocker cells (CPU, deterministic).**  `MockerCellSim` is a
  virtual-clock port of `llm/mocker/engine.py:MockEngine._step` —
  watermark admission, FCFS chunked prefill under the batched-token
  budget, one decode token per step per sequence, prefix-cache hits
  skipping prefill — with the feature axes folded into the timing
  constants via assumed ratios (`INT8_TRAFFIC_RATIO` etc. below; none
  measured on the chip, ROADMAP S6 and D13).
  No sleeping, no wall clock: frontiers are bit-reproducible, so tests
  pin exact capacity answers.
- **Real engines (TPU).**  `engine_frontier` drives `EngineCore`
  closed-loop over a concurrency grid (via
  `planner/profiler.py:cell_core_factory` for the feature axes).  It
  has never run on the chip: what the chip says of the system is in
  `PERF_LEDGER.jsonl`, measured by `chipbench/`.

Note on the disagg axis (ISSUE 16): the `disagg=True` cells here are
still *modeled* (the simulator folds the P/D split into its timing
constants), but a disagg cell is now MEASURABLE end-to-end — the slice
topology plane (`dynamo_tpu/fleet/topology.py`) runs a real
heterogeneous prefill/decode pair with different meshes and
byte-identical output (`tests/test_reshard_grid.py::
test_heterogeneous_disagg_serves_oracle_output`).  Wiring such a cell into this sweep
(replacing the modeled constants for `disagg=True`) is the remaining
depth carried on ROADMAP item 4.

Validation rides the observability plane: `run_fleet` drives N real
`MockEngine` workers (each with its own `/metrics` + `/debug/slo`
status server registered under `status_endpoints/`) under generated
load, and the modeled frontier is cross-checked against TTFT/TPOT
scraped via `tools/dynamo_top.py --once --json`.  The mocker runs the
SAME derived timing the simulator uses (`mock_args_for_cell`), so
model-vs-fleet agreement is a real check of the queueing model, not of
shared constants alone.  Documented tolerance: modeled and scraped
quantiles agree within `AGREEMENT_FACTOR` (×2) — scraped values are
bucket upper bounds (we register fine ×1.3-spaced buckets) and the
asyncio fleet adds event-loop scheduling jitter on top of simulated
step time.

    # CPU smoke: tiny grids, mocker cells, writes sla_profile.json and
    # prints the pinned capacity answer
    python -m benchmarks.sla_profiler --smoke

    # capacity planning: a million users at 6 requests/user/hour under
    # a 300ms/30ms SLO on agentic traffic
    python -m benchmarks.sla_profiler --users 1e6 --rph 6 \\
        --ttft-p99 0.3 --tpot-p99 0.03 --mix agentic

    # fleet-scale validation: 100 mocker workers scraped via dynamo_top
    python -m benchmarks.sla_profiler --fleet 100
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.data_generator.synthesizer import (
    TraceRecord,
    synthesize_prefix_heavy,
)
from dynamo_tpu.runtime.contracts import never_engine_thread

# -- feature-axis speed ratios (assumed) ----------------------------------
#
# The simulator's timing model starts from the mocker's v5e-ish constants
# (MockEngineArgs) and folds each feature in via one ratio.  Only the
# first is arithmetic on the cache's own bytes; no other has been measured
# on the chip (ROADMAP S6 lists each as a claim for a cell to measure).
INT8_TRAFFIC_RATIO = 0.53      # int8 KV bytes over bf16's, scales included
SPEC_DECODE_SPEEDUP = 1.3      # assumed decode speedup of n-gram drafting
PACKED_PREFILL_SPEEDUP = 1.3   # assumed: packed vs padded prefill
TP_PER_CHIP_RATIO = 0.91       # assumed: sharded tok/s/chip vs meshless
# MoE decode (PR 17): the dense oracle streams all E experts' weights
# per step — E/k = 4x the active-weight bytes at the default 8-expert
# top-2 geometry; the grouped kernel is assumed to win back 1.5x.
MOE_DENSE_WEIGHT_FACTOR = 4.0
MOE_GROUPED_SPEEDUP = 1.5
# Disaggregated P/D: eager KV streaming hides the transfer behind
# prefill (assumed overlap at least 0.5), so decode-side TTFT pays only the
# residual tail — modeled as a fixed hop plus a per-token tail rate.
DISAGG_TAIL_BASE_MS = 0.5
DISAGG_TAIL_MS_PER_TOKEN = 0.002

# Modeled-vs-scraped agreement tolerance for fleet validation: a ratio
# bound for queueing-dominated latencies (scraped quantiles are bucket
# upper bounds, ×1.3 spacing below, and the asyncio mocker adds per-step
# event-loop overhead the virtual clock doesn't model) plus an absolute
# floor for the overhead-dominated sub-10ms regime (see `agreement`).
AGREEMENT_FACTOR = 2.0
AGREEMENT_ATOL_S = 0.010

# Fine latency buckets for fleet workers: LATENCY_BUCKETS' ~2.5× spacing
# would dominate the agreement tolerance; ×1.3 spacing from 0.5 ms keeps
# bucket quantization under ~30%.
FINE_LATENCY_BUCKETS = tuple(0.0005 * 1.3 ** i for i in range(40))

PROFILE_SCHEMA_VERSION = 2

# A latency curve must climb at least this much (seconds) end-to-end to
# have a knee: sub-0.1ms "rises" are measurement texture, and the
# relative 1.3x guard alone divides by ~zero on curves touching 0.0.
KNEE_MIN_RISE_S = 1e-4


# -- sweep cells ---------------------------------------------------------


@dataclass(frozen=True)
class CellConfig:
    """One sweep configuration over the serving feature axes.

    A cell is the unit deployment the capacity model replicates:
    `workers` engines, each on a `tp×ep`-chip mesh; `disagg` adds an
    equal pool of prefill workers (the PAPER.md "prefill slice + decode
    slice" shape).  `moe` selects the model family AND the serving
    mode: "off" (dense model), "dense" (MoE via the every-expert
    oracle) or "grouped" (MoE via the grouped fast path, PR 17); `ep`
    shards the expert weights across chips and is only meaningful on
    MoE cells."""

    name: str
    tp: int = 1
    ep: int = 1                    # expert-parallel degree (MoE cells)
    workers: int = 1
    packed_prefill: bool = False
    kv_quant: str = "none"         # "none" | "int8"
    spec_decode: int = 0           # draft length; 0 = off
    disagg: bool = False
    moe: str = "off"               # "off" | "dense" | "grouped"

    def __post_init__(self):
        if self.moe not in ("off", "dense", "grouped"):
            raise ValueError(
                f"cell {self.name!r}: moe={self.moe!r} not in "
                f"('off', 'dense', 'grouped')")
        if self.ep > 1 and self.moe == "off":
            raise ValueError(
                f"cell {self.name!r}: ep={self.ep} shards expert "
                f"weights — meaningless on a dense (moe='off') cell")

    @property
    def chips(self) -> int:
        return self.tp * self.ep * self.workers * (2 if self.disagg else 1)

    def to_dict(self) -> Dict:
        d = asdict(self)
        d["chips"] = self.chips
        return d


@dataclass(frozen=True)
class CellTiming:
    """Per-worker simulated timing constants after folding a cell's
    feature axes into the mocker's base model."""

    prefill_ms_per_token: float
    decode_base_ms: float
    decode_ms_per_seq: float
    max_batched_tokens: int
    max_num_seqs: int
    block_size: int


# Mocker base constants (MockEngineArgs defaults — loosely a v5e curve).
_BASE_PREFILL_MS_PER_TOKEN = 0.35
_BASE_DECODE_BASE_MS = 4.0
_BASE_DECODE_MS_PER_SEQ = 0.05


def _tp_speedup(tp: int) -> float:
    """Total speedup of a tp-way mesh: linear × the gate-proven per-chip
    efficiency (0.91 per doubling — PR 9's tok_s_per_chip_ratio)."""
    if tp <= 1:
        return 1.0
    return tp * TP_PER_CHIP_RATIO ** math.log2(tp)


def cell_timing(cell: CellConfig, *, block_size: int = 32,
                max_batched_tokens: int = 8192,
                max_num_seqs: int = 256) -> CellTiming:
    """Fold the cell's feature axes into per-worker timing constants.

    - tp divides all compute/bandwidth terms by `_tp_speedup`;
    - packed prefill divides the per-token prefill cost (PR 10);
    - int8 KV scales the PER-SEQUENCE decode term (the KV-bandwidth
      part) by the traffic ratio — the base term models launch +
      weight-read cost quantization doesn't touch;
    - spec decode divides both decode terms by the modeled speedup
      (more tokens per verified dispatch);
    - MoE multiplies the weight-read terms (prefill per-token + decode
      base — the terms expert weights live in, not the KV per-seq term)
      by the expert-traffic factor: the dense oracle pays the full
      E/k = 4x blowup, the grouped path claws back the gate-proven
      1.5x, and ep shards the expert stream across chips on the same
      per-chip efficiency curve as tp.  The factor is floored at 1.0 —
      ep shards only the expert weights, so no MoE cell beats the
      equivalent dense-model cell.
    """
    s_tp = _tp_speedup(cell.tp)
    ppt = _BASE_PREFILL_MS_PER_TOKEN / s_tp
    if cell.packed_prefill:
        ppt /= PACKED_PREFILL_SPEEDUP
    base = _BASE_DECODE_BASE_MS / s_tp
    per_seq = _BASE_DECODE_MS_PER_SEQ / s_tp
    if cell.moe != "off":
        f = MOE_DENSE_WEIGHT_FACTOR
        if cell.moe == "grouped":
            f /= MOE_GROUPED_SPEEDUP
        f = max(1.0, f / _tp_speedup(cell.ep))
        ppt *= f
        base *= f
    if cell.kv_quant == "int8":
        per_seq *= INT8_TRAFFIC_RATIO
    if cell.spec_decode > 0:
        base /= SPEC_DECODE_SPEEDUP
        per_seq /= SPEC_DECODE_SPEEDUP
    return CellTiming(
        prefill_ms_per_token=ppt,
        decode_base_ms=base,
        decode_ms_per_seq=per_seq,
        max_batched_tokens=max_batched_tokens,
        max_num_seqs=max_num_seqs,
        block_size=block_size)


def mock_args_for_cell(cell: CellConfig, *, block_size: int = 32,
                       num_blocks: int = 16_384,
                       speedup_ratio: float = 1.0):
    """MockEngineArgs carrying the SAME derived timing the simulator
    uses, so a fleet of real MockEngines running this cell is the
    simulator's ground truth (fleet validation closes the loop through
    the real async engine + metrics + dynamo_top, not through shared
    code)."""
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs

    t = cell_timing(cell, block_size=block_size)
    return MockEngineArgs(
        num_blocks=num_blocks, block_size=block_size,
        max_num_seqs=t.max_num_seqs,
        max_batched_tokens=t.max_batched_tokens,
        speedup_ratio=speedup_ratio,
        prefill_ms_per_token=t.prefill_ms_per_token,
        decode_base_ms=t.decode_base_ms,
        decode_ms_per_seq=t.decode_ms_per_seq)


def default_cells() -> List[CellConfig]:
    """The sweep grid: every feature plane PRs 6-10 shipped, alone and
    composed, at one and two chips per worker."""
    return [
        CellConfig("base"),
        CellConfig("int8", kv_quant="int8"),
        CellConfig("spec", spec_decode=4),
        CellConfig("packed", packed_prefill=True),
        CellConfig("int8+spec+packed", kv_quant="int8", spec_decode=4,
                   packed_prefill=True),
        CellConfig("tp2-fast", tp=2, kv_quant="int8", spec_decode=4,
                   packed_prefill=True),
        CellConfig("disagg-fast", kv_quant="int8", spec_decode=4,
                   packed_prefill=True, disagg=True),
    ]


def moe_cells() -> List[CellConfig]:
    """The MoE sweep grid (PR 17): the dense oracle as the honesty
    baseline, the grouped fast path alone and composed with the PR 6/10
    serving planes, and ep-sharded expert variants.  Swept under the
    `moe_agentic` mix so `plan_capacity` names a cheapest MoE fleet
    WITHOUT competing in (or perturbing) the dense-model plan the smoke
    fixture pins."""
    return [
        CellConfig("moe-dense", moe="dense"),
        CellConfig("moe-grouped", moe="grouped"),
        CellConfig("moe-grouped+int8+spec+packed", moe="grouped",
                   kv_quant="int8", spec_decode=4, packed_prefill=True),
        CellConfig("moe-grouped-ep2", moe="grouped", ep=2),
        CellConfig("moe-grouped-ep2+int8+spec+packed", moe="grouped",
                   ep=2, kv_quant="int8", spec_decode=4,
                   packed_prefill=True),
    ]


# -- traffic mixes -------------------------------------------------------


TRAFFIC_MIXES = ("agentic", "long_context", "diurnal", "moe_agentic")


def make_traffic(mix: str, num_requests: int, *, block_size: int = 32,
                 seed: int = 0) -> List[TraceRecord]:
    """One of the named traffic shapes, as data-generator trace records.

    - `agentic`: prefix-heavy tool-call loops — few deep shared contexts
      (system prompt + tool schemas), short unique suffixes, short
      outputs; the KV-reuse-dominated regime.
    - `long_context`: long unshared prompts, modest outputs — the
      prefill-bound regime ring-SP exists for.
    - `diurnal`: the agentic shape with sinusoidally-modulated arrival
      intervals (AR(p)-predictable bursty load, planner/predictor.py) —
      peak rate ~3x trough.
    - `moe_agentic`: the agentic ARRIVAL shape served by an MoE model —
      the regime PR 17's fast-decode plane targets.  Same trace records
      (traffic shape is a property of the workload, not the model); the
      mix name keys the planner to the `moe_cells()` grid so the MoE
      capacity plan is answered per-mix, beside — never inside — the
      dense-model plan.

    Timestamps are a base pacing; `scale_to_rate` rescales them to an
    offered load before simulation/replay.
    """
    if mix in ("agentic", "moe_agentic"):
        return synthesize_prefix_heavy(
            num_requests, num_roots=max(2, num_requests // 16),
            context_blocks=6, suffix_tokens=24, output_tokens=16,
            interval_ms=20.0, block_size=block_size, seed=seed)
    if mix == "long_context":
        # Unique hash ids per request: no sharing, all prefill.
        out = []
        for i in range(num_requests):
            ids = [1_000_000_007 * (seed + 1) + i * 64 + b
                   for b in range(12)]
            out.append(TraceRecord(
                timestamp=i * 40.0, input_length=12 * block_size + 16,
                output_length=16, hash_ids=ids))
        return out
    if mix == "diurnal":
        base = synthesize_prefix_heavy(
            num_requests, num_roots=max(2, num_requests // 16),
            context_blocks=6, suffix_tokens=24, output_tokens=16,
            interval_ms=20.0, block_size=block_size, seed=seed)
        # Modulate inter-arrival gaps over two full periods: rate swings
        # 1/2x..2x the mean, so the same record count covers trough and
        # burst.
        t = 0.0
        out = []
        for i, rec in enumerate(base):
            phase = 2.0 * math.pi * (2.0 * i / max(len(base) - 1, 1))
            gap = 20.0 / (1.25 + 0.75 * math.sin(phase))
            t += gap
            out.append(TraceRecord(
                timestamp=t, input_length=rec.input_length,
                output_length=rec.output_length, hash_ids=rec.hash_ids))
        return out
    raise ValueError(f"unknown traffic mix {mix!r} "
                     f"(have {', '.join(TRAFFIC_MIXES)})")


def scale_to_rate(records: List[TraceRecord],
                  rps: float) -> List[TraceRecord]:
    """Rescale timestamps so the mean offered rate is `rps`, preserving
    the arrival SHAPE (diurnal bursts stay bursts)."""
    if not records or rps <= 0:
        return list(records)
    span_ms = records[-1].timestamp - records[0].timestamp
    if span_ms <= 0:
        return list(records)
    current = (len(records) - 1) / (span_ms / 1000.0)
    f = current / rps
    t0 = records[0].timestamp
    return [TraceRecord(timestamp=(r.timestamp - t0) * f,
                        input_length=r.input_length,
                        output_length=r.output_length,
                        hash_ids=r.hash_ids)
            for r in records]


# -- the mocker-cell simulator ------------------------------------------


@dataclass
class _SimSeq:
    isl: int
    osl: int
    blocks: Tuple                  # block identities for prefix-cache hits
    t_arrival: float
    prefilled: int = 0
    out: int = 0
    decoding: bool = False
    t_first: float = 0.0           # first token EMITTED (step start)
    t_first_busy: float = 0.0      # prefill-work complete (step end)
    t_done: float = 0.0


@dataclass
class SimStats:
    """Per-run latency + load aggregates, all in simulated seconds.

    `ttft_s` uses the mocker's EMISSION clock: `MockEngine._step`
    computes the step and puts tokens on the queues, then sleeps the
    simulated step latency — so the wall clock a fleet driver (and
    dynamo_top) observes sees first tokens at step START, with the
    step's latency charged to everything queued behind it.  Validation
    must mirror that.  `ttft_busy_s` is the conventional
    "prefill work finished" time (step END) — what the planner's
    interpolation grids mean by TTFT."""

    ttft_s: List[float] = field(default_factory=list)
    ttft_busy_s: List[float] = field(default_factory=list)
    tpot_s: List[float] = field(default_factory=list)
    duration_s: float = 0.0
    output_tokens: int = 0
    mean_inflight: float = 0.0


class MockerCellSim:
    """Virtual-clock port of `MockEngine._step` for ONE worker.

    Semantics mirrored exactly (so fleet validation measures queueing
    fidelity, not model drift): FCFS admission up to `max_num_seqs`,
    prefix-cache hits skip prefill work (`prefilled = min(cached,
    isl-1)`), chunked prefill FCFS under the batched-token budget, first
    token emitted the step prefill completes, every other decoding
    sequence advances one token per step, step latency =
    prefill_tokens·ppt + (base + per_seq·n_decoding), charged AFTER
    emission (the mocker's emit-then-sleep order — see SimStats).

    One documented difference: the KV pool is assumed non-binding
    (capacity generous vs the workload, as in the fleet runs) so
    admission never blocks on the watermark.
    """

    def __init__(self, timing: CellTiming) -> None:
        self.t = timing

    def run(self, arrivals: Sequence[Tuple[float, _SimSeq]]) -> SimStats:
        """`arrivals`: (t_ms, seq) sorted by time.  Returns stats over
        all completed sequences."""
        pending = sorted(arrivals, key=lambda a: a[0])
        running: List[_SimSeq] = []
        seen_blocks: set = set()
        clock = 0.0
        stats = SimStats()
        inflight_ms = 0.0
        i = 0
        while i < len(pending) or running:
            if not running and i < len(pending):
                clock = max(clock, pending[i][0])
            # Admit everything that has arrived (FCFS, slot-bounded).
            while (i < len(pending) and pending[i][0] <= clock
                   and len(running) < self.t.max_num_seqs):
                seq = pending[i][1]
                i += 1
                cached = 0
                for b in seq.blocks:
                    if b in seen_blocks:
                        cached += 1
                    else:
                        break          # prefix hits are contiguous
                seen_blocks.update(seq.blocks)
                seq.prefilled = max(seq.prefilled,
                                    min(cached * self.t.block_size,
                                        seq.isl - 1))
                running.append(seq)

            # One step: chunked prefill FCFS, then decode.
            budget = self.t.max_batched_tokens
            prefill_tokens = 0
            first_token = []
            for s in running:
                if s.decoding or budget <= 0:
                    continue
                chunk = min(s.isl - s.prefilled, budget)
                s.prefilled += chunk
                budget -= chunk
                prefill_tokens += chunk
                if s.prefilled >= s.isl:
                    s.decoding = True
                    first_token.append(s)
            decoding = [s for s in running if s.decoding]
            step_ms = prefill_tokens * self.t.prefill_ms_per_token
            if decoding:
                step_ms += (self.t.decode_base_ms
                            + self.t.decode_ms_per_seq * len(decoding))
            # Emission happens at step START (clock), the simulated
            # latency is slept AFTER — mirror before advancing.
            done = []
            for s in decoding:
                if s in first_token:
                    s.out = 1
                    s.t_first = clock
                    s.t_first_busy = clock + step_ms
                else:
                    s.out += 1
                if s.out >= s.osl:
                    s.t_done = clock
                    done.append(s)
            clock += step_ms
            inflight_ms += len(running) * step_ms
            for s in done:
                running.remove(s)
                stats.ttft_s.append((s.t_first - s.t_arrival) / 1e3)
                stats.ttft_busy_s.append(
                    (s.t_first_busy - s.t_arrival) / 1e3)
                if s.osl > 1:
                    stats.tpot_s.append(
                        (s.t_done - s.t_first) / (s.osl - 1) / 1e3)
                stats.output_tokens += s.osl
        stats.duration_s = clock / 1e3
        stats.mean_inflight = inflight_ms / clock if clock > 0 else 0.0
        return stats


def _record_blocks(rec: TraceRecord, block_size: int,
                   uid: int) -> Tuple:
    """Block identities matching the mocker's chained-hash reuse: the
    hashed prefix blocks are shared (identity = the hash_ids chain so
    far), tail blocks past the prefix are unique per request."""
    ids: List = []
    for k in range(len(rec.hash_ids)):
        ids.append(tuple(rec.hash_ids[:k + 1]))
    tail_blocks = rec.input_length // block_size - len(rec.hash_ids)
    for k in range(max(0, tail_blocks)):
        ids.append(("uniq", uid, k))
    return tuple(ids)


def simulate_cell(cell: CellConfig, records: List[TraceRecord],
                  *, block_size: int = 32) -> SimStats:
    """Run one cell (all `cell.workers` workers, round-robin arrivals)
    over a trace; aggregate stats across workers.

    Disaggregated cells run prefill and decode pools separately:
    prefill workers serve the prompt (ttft = prefill completion +
    modeled eager-transfer tail), decode workers serve the output with
    no prefill interference."""
    timing = cell_timing(cell, block_size=block_size)
    per_worker: List[List[Tuple[float, _SimSeq]]] = [
        [] for _ in range(cell.workers)]
    for i, rec in enumerate(records):
        seq = _SimSeq(isl=rec.input_length, osl=rec.output_length,
                      blocks=_record_blocks(rec, block_size, i),
                      t_arrival=rec.timestamp)
        per_worker[i % cell.workers].append((rec.timestamp, seq))

    if not cell.disagg:
        agg = SimStats()
        for arrivals in per_worker:
            if not arrivals:
                continue
            s = MockerCellSim(timing).run(arrivals)
            agg.ttft_s += s.ttft_s
            agg.ttft_busy_s += s.ttft_busy_s
            agg.tpot_s += s.tpot_s
            agg.output_tokens += s.output_tokens
            agg.duration_s = max(agg.duration_s, s.duration_s)
            agg.mean_inflight += s.mean_inflight
        return agg

    # Disagg: prefill pool first (osl=1 → time-to-first-token), then the
    # decode pool sees arrivals at prefill-done + transfer tail, with
    # the prompt already resident (prefilled = isl-1, one admission
    # chunk — the decode side's 1-token "prefill", as in the real plane).
    agg = SimStats()
    for arrivals in per_worker:
        if not arrivals:
            continue
        pre = [(t, _SimSeq(isl=s.isl, osl=1, blocks=s.blocks,
                           t_arrival=t))
               for t, s in arrivals]
        ps = MockerCellSim(timing).run(pre)
        decode_arrivals = []
        for (t, s), (_, pseq) in zip(arrivals, pre):
            tail_ms = (DISAGG_TAIL_BASE_MS
                       + DISAGG_TAIL_MS_PER_TOKEN * s.isl
                       * (INT8_TRAFFIC_RATIO
                          if cell.kv_quant == "int8" else 1.0))
            # pseq.t_first_busy is the prefill worker's work-complete
            # clock for THIS request (run() fills it in-place, so order
            # is safe) — the KV is transferable only after the work, not
            # at the mocker's early emission.
            t_dec = pseq.t_first_busy + tail_ms
            dseq = _SimSeq(isl=s.isl, osl=s.osl, blocks=s.blocks,
                           t_arrival=t)
            dseq.prefilled = s.isl - 1
            decode_arrivals.append((t_dec, dseq))
        decode_arrivals.sort(key=lambda a: a[0])
        ds = MockerCellSim(timing).run(decode_arrivals)
        agg.ttft_s += ds.ttft_s
        agg.ttft_busy_s += ds.ttft_busy_s
        agg.tpot_s += ds.tpot_s
        agg.output_tokens += ds.output_tokens
        agg.duration_s = max(agg.duration_s, ds.duration_s)
        agg.mean_inflight += ds.mean_inflight + ps.mean_inflight
    return agg


# -- frontier sweep + knee detection ------------------------------------


@dataclass
class FrontierPoint:
    offered_rps: float
    ttft_p50_s: float
    ttft_p99_s: float
    tpot_p50_s: float
    tpot_p99_s: float
    throughput_tok_s: float
    mean_inflight: float

    def to_dict(self) -> Dict:
        return {k: round(v, 6) for k, v in asdict(self).items()}


@dataclass
class CellFrontier:
    cell: CellConfig
    mix: str
    points: List[FrontierPoint]
    knee_idx: Optional[int]

    @property
    def knee(self) -> Optional[FrontierPoint]:
        return (self.points[self.knee_idx]
                if self.knee_idx is not None else None)

    def to_dict(self) -> Dict:
        return {
            "config": self.cell.to_dict(),
            "mix": self.mix,
            "points": [p.to_dict() for p in self.points],
            "knee_idx": self.knee_idx,
            "knee": self.knee.to_dict() if self.knee else None,
        }


def find_knee(loads: Sequence[float],
              latencies: Sequence[float]) -> Optional[int]:
    """Saturation knee of a latency-vs-load curve (kneedle, convex
    increasing form): normalize both axes to [0,1] and take the argmax
    of x̂ - ŷ — the point of maximum distance below the chord, where
    the curve turns from flat to climbing.

    Returns None when the curve never saturates in the measured range
    (max latency under 1.3× min, or a total rise under KNEE_MIN_RISE_S
    — the relative guard alone is defeated by curves touching 0.0,
    e.g. emission-clock TTFT at light load; a flat or still-linear
    curve has no knee to report, and inventing one would let the
    capacity model "cap" at an arbitrary load)."""
    if len(loads) != len(latencies):
        raise ValueError("loads and latencies must align")
    if len(loads) < 3:
        return None
    x = np.asarray(loads, np.float64)
    y = np.asarray(latencies, np.float64)
    if not np.all(np.diff(x) > 0):
        raise ValueError("loads must be strictly increasing")
    if (y.max() < 1.3 * max(y.min(), 1e-12)
            or y.max() - y.min() < KNEE_MIN_RISE_S):
        return None
    xn = (x - x[0]) / (x[-1] - x[0])
    yn = (y - y.min()) / (y.max() - y.min())
    return int(np.argmax(xn - yn))


def closed_loop_knee(points: Sequence[FrontierPoint]) -> Optional[int]:
    """Knee of a CLOSED-loop frontier (engine_frontier): offered_rps =
    conc/wall, which plateaus or dips once the engine saturates, so the
    raw load axis violates find_knee's strictly-increasing contract at
    exactly the operating point the sweep exists to find.  Run kneedle
    on the strictly-increasing prefix; if the curve was truncated (a
    plateau exists) and the prefix itself shows no knee, the last
    point still on the rise IS the saturation onset — report it."""
    loads = [p.offered_rps for p in points]
    n = 1
    while n < len(loads) and loads[n] > loads[n - 1]:
        n += 1
    truncated = n < len(loads)
    if n >= 3:
        k = find_knee(loads[:n],
                      [p.ttft_p99_s for p in points[:n]])
        if k is not None:
            return k
    return n - 1 if truncated else None


def percentile(vals: Sequence[float], q: float) -> float:
    if not vals:
        return 0.0
    return float(np.percentile(np.asarray(vals, np.float64), q))


def profile_cell(cell: CellConfig, mix: str, loads_rps: Sequence[float],
                 *, num_requests: int = 96, block_size: int = 32,
                 seed: int = 0) -> CellFrontier:
    """The frontier of one cell under one traffic mix: simulate the mix
    rescaled to each offered load, summarize latency quantiles, and
    find the knee on the TTFT-p99 curve.

    Offered load is FLEET load for the cell (its `workers` engines
    share it round-robin), so `knee.offered_rps` is directly the
    per-replica capacity the planner multiplies."""
    base = make_traffic(mix, num_requests, block_size=block_size,
                        seed=seed)
    points = []
    for rps in loads_rps:
        records = scale_to_rate(base, rps)
        s = simulate_cell(cell, records, block_size=block_size)
        points.append(FrontierPoint(
            offered_rps=float(rps),
            ttft_p50_s=percentile(s.ttft_s, 50),
            ttft_p99_s=percentile(s.ttft_s, 99),
            tpot_p50_s=percentile(s.tpot_s, 50),
            tpot_p99_s=percentile(s.tpot_s, 99),
            throughput_tok_s=(s.output_tokens / s.duration_s
                              if s.duration_s > 0 else 0.0),
            mean_inflight=s.mean_inflight))
    knee = find_knee([p.offered_rps for p in points],
                     [p.ttft_p99_s for p in points])
    return CellFrontier(cell=cell, mix=mix, points=points, knee_idx=knee)


# -- interpolator-compatible micro-profile ------------------------------


def cell_micro_profile(cell: CellConfig, *,
                       isl_grid: Sequence[int] = (128, 256, 512),
                       context_grid: Sequence[int] = (256, 512, 1024),
                       kv_grid: Sequence[float] = (0.2, 0.5, 0.8),
                       decode_tokens: int = 32,
                       num_blocks: int = 2048,
                       block_size: int = 32) -> Dict:
    """The exact `prefill`/`decode` grids `PrefillInterpolator` /
    `DecodeInterpolator` consume, measured on the cell simulator — the
    same sweep shape as `planner/profiler.py:profile_engine`, per-worker
    (the planner's per-chip units divide by `cell.tp`)."""
    timing = cell_timing(cell, block_size=block_size)
    prefill = {"isl": [], "ttft_s": [], "tok_s_per_chip": []}
    for isl in isl_grid:
        seq = _SimSeq(isl=int(isl), osl=1, blocks=(), t_arrival=0.0)
        s = MockerCellSim(timing).run([(0.0, seq)])
        ttft = s.ttft_busy_s[0]   # prefill WORK time, not early emission
        prefill["isl"].append(int(isl))
        prefill["ttft_s"].append(ttft)
        prefill["tok_s_per_chip"].append(
            isl / ttft / cell.tp if ttft > 0 else 0.0)

    decode = {"kv_usage": [float(k) for k in kv_grid],
              "context": [int(c) for c in context_grid],
              "itl_s": [], "tok_s_per_chip": []}
    for ctx in context_grid:
        itl_row, thpt_row = [], []
        pages_per_seq = (ctx + block_size - 1) // block_size + 1
        for kv in kv_grid:
            batch = max(1, int(kv * (num_blocks - 1) / pages_per_seq))
            batch = min(batch, timing.max_num_seqs)
            arrivals = []
            for b in range(batch):
                arrivals.append((0.0, _SimSeq(
                    isl=int(ctx), osl=decode_tokens,
                    blocks=(("d", ctx, kv, b),), t_arrival=0.0)))
            s = MockerCellSim(timing).run(arrivals)
            itl_row.append(percentile(s.tpot_s, 50))
            decode_s = max(s.duration_s - percentile(s.ttft_busy_s, 50),
                           1e-9)
            thpt_row.append(s.output_tokens / decode_s / cell.tp)
        decode["itl_s"].append(itl_row)
        decode["tok_s_per_chip"].append(thpt_row)
    return {"prefill": prefill, "decode": decode}


# -- capacity model ------------------------------------------------------


@dataclass(frozen=True)
class SloTarget:
    ttft_p99_s: float
    tpot_p99_s: float


@dataclass
class CapacityPlan:
    """The profiler's end-to-end answer: the cheapest fleet holding the
    SLO at the required load, or an explicit refusal naming why every
    config was rejected (a plan that silently under-delivers is how
    million-user fleets fall over)."""

    feasible: bool
    required_rps: float
    slo: SloTarget
    mix: str = ""
    cell: Optional[Dict] = None        # chosen cell config dict
    replicas: int = 0
    total_chips: int = 0
    per_replica_rps: float = 0.0
    headroom: float = 0.0              # 1 - required/(replicas*per_replica)
    rejected: List[Dict] = field(default_factory=list)

    def to_dict(self) -> Dict:
        d = asdict(self)
        d["slo"] = asdict(self.slo)
        return d


def sustainable_rps(frontier: CellFrontier,
                    slo: SloTarget) -> Tuple[float, str]:
    """Highest profiled load meeting the SLO, capped at the knee —
    beyond the knee the latency-vs-load slope explodes and interpolated
    headroom is fiction.  Returns (rps, reason); rps 0 with the reason
    when no point qualifies."""
    limit = (frontier.knee_idx if frontier.knee_idx is not None
             else len(frontier.points) - 1)
    best = 0.0
    worst = None
    for idx, p in enumerate(frontier.points):
        if idx > limit:
            break
        if p.ttft_p99_s <= slo.ttft_p99_s and p.tpot_p99_s <= slo.tpot_p99_s:
            best = max(best, p.offered_rps)
        elif worst is None:
            # First (lowest-load) failing point: when everything fails,
            # the refusal reason quotes the latency at MIN load — the
            # honest answer to "how far off is this config" (the
            # highest-load point would overstate the miss by the whole
            # saturation climb).
            worst = p
    if best > 0:
        return best, "ok"
    p = worst or frontier.points[0]
    return 0.0, (f"over SLO at min load: ttft_p99={p.ttft_p99_s:.4f}s "
                 f"(target {slo.ttft_p99_s}s), tpot_p99="
                 f"{p.tpot_p99_s:.4f}s (target {slo.tpot_p99_s}s)")


def plan_capacity(frontiers: Sequence[CellFrontier], slo: SloTarget,
                  required_rps: float, *,
                  max_replicas: int = 100_000) -> CapacityPlan:
    """Name the cheapest fleet: for every profiled cell, the highest
    SLO-meeting load below the knee sets its per-replica capacity;
    replicas = ceil(required / capacity); cost = replicas × chips.
    Minimum cost wins, headroom breaks ties.  Refuses (feasible=False)
    when no cell holds the SLO at any profiled load — the over-SLO
    configs are listed with the latency that sank them."""
    candidates = []
    rejected = []
    for f in frontiers:
        rps, reason = sustainable_rps(f, slo)
        if rps <= 0:
            rejected.append({"cell": f.cell.name, "mix": f.mix,
                             "reason": reason})
            continue
        replicas = max(1, math.ceil(required_rps / rps))
        if replicas > max_replicas:
            rejected.append({"cell": f.cell.name, "mix": f.mix,
                             "reason": f"needs {replicas} replicas "
                                       f"(> max {max_replicas})"})
            continue
        chips = replicas * f.cell.chips
        headroom = 1.0 - required_rps / (replicas * rps)
        # Cell name as the last comparable key: full ties stay
        # deterministic across runs (the pinned-fixture contract).
        candidates.append((chips, replicas, -headroom, f.cell.name,
                           f, rps))
    if not candidates:
        return CapacityPlan(feasible=False, required_rps=required_rps,
                            slo=slo, rejected=rejected)
    chips, replicas, neg_head, _, f, rps = min(
        candidates, key=lambda c: c[:4])
    return CapacityPlan(
        feasible=True, required_rps=required_rps, slo=slo, mix=f.mix,
        cell=f.cell.to_dict(), replicas=replicas, total_chips=chips,
        per_replica_rps=rps, headroom=-neg_head, rejected=rejected)


# -- profile assembly ----------------------------------------------------


def build_profile(frontiers: Sequence[CellFrontier], *,
                  base_cell: Optional[CellConfig] = None,
                  plan: Optional[CapacityPlan] = None,
                  micro_kw: Optional[Dict] = None) -> Dict:
    """Assemble the planner profile: the v1 `prefill`/`decode` grids
    (from `base_cell`, default the first swept cell) plus the v2 `meta`
    block — per-cell frontiers, knees, the capacity plan, and the
    knee concurrency `tools/dynamo_top.py --profile` renders as live
    capacity headroom.  `SlaPlanner(profile)` consumes this dict
    unchanged; `meta` is invisible to the interpolators."""
    cells = list(frontiers)
    if not cells:
        raise ValueError("no frontiers to build a profile from")
    base = base_cell or cells[0].cell
    profile = cell_micro_profile(base, **(micro_kw or {}))
    # Per-worker knee concurrency of the cell the operator will
    # actually DEPLOY — the plan's winner when there is one (dynamo_top
    # HEADRM measures live workers against this; the base cell's knee
    # would misjudge a faster deployed config as overloaded).  Fall
    # back to the first kneed cell for plan-less sweeps.
    ordered = list(cells)
    if plan and plan.feasible and plan.cell:
        ordered.sort(key=lambda f: f.cell.name != plan.cell["name"])
    knee_conc = None
    for f in ordered:
        if f.knee is not None:
            knee_conc = f.knee.mean_inflight / f.cell.workers
            break
    profile["meta"] = {
        "schema_version": PROFILE_SCHEMA_VERSION,
        "generated_by": "benchmarks/sla_profiler",
        "base_cell": base.to_dict(),
        "cells": [f.to_dict() for f in cells],
        "capacity": {
            "knee_concurrency_per_worker": knee_conc,
            "plan": plan.to_dict() if plan else None,
        },
        "tolerance": {
            "fleet_agreement_factor": AGREEMENT_FACTOR,
            "fleet_agreement_atol_s": AGREEMENT_ATOL_S,
            "note": "modeled vs dynamo_top-scraped quantiles agree "
                    "within this factor, or this absolute bound when "
                    "overhead-dominated (bucket bounds + event-loop "
                    "jitter)",
        },
    }
    return profile


# -- real-engine frontier (TPU re-baselining vehicle) -------------------


# No thread contract here: like planner/profiler.py:profile_engine,
# this loop IS the engine-driving thread (synchronous add_request/step),
# so @never_engine_thread would conflict with @engine_thread_only.
def engine_frontier(make_core, concurrency_grid: Sequence[int], *,
                    isl: int = 256, osl: int = 32,
                    seed: int = 0) -> List[FrontierPoint]:
    """Closed-loop frontier on a REAL EngineCore: for each concurrency,
    submit C distinct prompts, drain prefill (excluded from the decode
    window via `has_pending_prefill`), then step to completion measuring
    per-request TTFT/TPOT in wall time.  Each point runs twice on a
    fresh core and keeps the second (compile-free) measurement — the
    same discipline as `planner/profiler.py:profile_engine`.

    With `planner/profiler.py:cell_core_factory` supplying cores per
    CellConfig, this is the TPU half of the sweep — and the designated
    re-baselining vehicle now that BENCH_r*.json ends at r05."""
    import time as _time

    from dynamo_tpu.engine.sampling import SamplingParams

    points = []
    for conc in concurrency_grid:
        core = make_core()
        vocab = core.config.model.vocab_size
        ttfts: List[float] = []
        tpots: List[float] = []
        wall = 0.0
        produced = 0
        for attempt in range(2):   # warm (pays XLA compiles), measure
            rng = np.random.default_rng(seed * 91 + conc * 7 + attempt)
            for c in range(conc):
                core.add_request(
                    f"f{attempt}-{c}",
                    rng.integers(1, vocab, size=isl).tolist(),
                    SamplingParams(max_tokens=osl))
            t_submit = _time.perf_counter()
            first: Dict[str, float] = {}
            last: Dict[str, float] = {}
            counts: Dict[str, int] = {}

            def ingest(deltas):
                now = _time.perf_counter()
                for d in deltas:
                    if not d.token_ids:
                        continue
                    first.setdefault(d.request_id, now)
                    last[d.request_id] = now
                    counts[d.request_id] = (counts.get(d.request_id, 0)
                                            + len(d.token_ids))

            # Split so the prefill drain is visible in profiles — and so
            # the public has_pending_prefill property (not _requests) is
            # what external drivers key on.
            while core.has_pending_prefill:
                ingest(core.step())
            while core.has_work:
                ingest(core.step())
            wall = _time.perf_counter() - t_submit
            ttfts = [t - t_submit for t in first.values()]
            tpots = [(last[r] - first[r]) / max(counts[r] - 1, 1)
                     for r in first if counts.get(r, 0) > 1]
            produced = sum(counts.values())
        points.append(FrontierPoint(
            offered_rps=conc / wall if wall > 0 else 0.0,
            ttft_p50_s=percentile(ttfts, 50),
            ttft_p99_s=percentile(ttfts, 99),
            tpot_p50_s=percentile(tpots, 50),
            tpot_p99_s=percentile(tpots, 99),
            throughput_tok_s=produced / wall if wall > 0 else 0.0,
            mean_inflight=float(conc)))
    return points


# -- fleet validation over the observability plane ----------------------


@never_engine_thread
async def run_fleet(cell: CellConfig, records: List[TraceRecord], *,
                    num_workers: int, block_size: int = 32,
                    slo: Optional[SloTarget] = None,
                    speedup_ratio: float = 1.0):
    """Drive `num_workers` REAL MockEngines under the trace, each with
    its own metrics registry + SLO monitor + status server registered
    under `status_endpoints/` on a fresh control plane — the exact
    plane `tools/dynamo_top.py` discovers and scrapes.

    Arrivals pace open-loop in wall time (speedup_ratio compresses the
    mocker's simulated hardware AND the pacing together, so latency
    ratios survive compression; observed latencies are multiplied back
    by the ratio before entering the histograms — the scrape reads
    simulated seconds either way).  Returns (cp_port, summary,
    teardown): callers scrape via dynamo_top before awaiting teardown.
    """
    import asyncio
    import time as _time

    from benchmarks.data_generator.synthesizer import tokens_for_record
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.llm.mocker.engine import MockEngine
    from dynamo_tpu.llm.preprocessor import PreprocessedRequest
    from dynamo_tpu.runtime.control_plane_tcp import (
        ControlPlaneClient,
        ControlPlaneServer,
    )
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.slo import (
        SloMonitor,
        SloObjective,
        latency_source,
    )
    from dynamo_tpu.runtime.status import (
        StatusServer,
        register_status_endpoint,
    )

    srv = ControlPlaneServer()
    cp_port = await srv.start()
    cp = ControlPlaneClient("127.0.0.1", cp_port)
    await cp.start()

    workers = []
    for w in range(num_workers):
        eng = MockEngine(mock_args_for_cell(
            cell, block_size=block_size, speedup_ratio=speedup_ratio))
        reg = MetricsRegistry()
        ttft_h = reg.histogram("request_ttft_seconds",
                               "Request time to first token",
                               buckets=FINE_LATENCY_BUCKETS)
        tpot_h = reg.histogram("request_tpot_seconds",
                               "Per-output-token interval",
                               buckets=FINE_LATENCY_BUCKETS)
        mon = None
        if slo is not None:
            mon = SloMonitor(
                [(SloObjective("ttft_p99", threshold_s=slo.ttft_p99_s),
                  latency_source(ttft_h, slo.ttft_p99_s)),
                 (SloObjective("tpot_p99", threshold_s=slo.tpot_p99_s),
                  latency_source(tpot_h, slo.tpot_p99_s))],
                registry=reg)

        def worker_text(e=eng) -> str:
            # The real worker's ForwardPassMetrics exposition (the INFL
            # column and dynamo_top's HEADRM read these).
            ws = e.metrics.worker_stats
            ks = e.metrics.kv_stats
            return (
                "dynamo_worker_request_active_slots "
                f"{ws.request_active_slots}\n"
                f"dynamo_worker_requests_waiting {ws.num_requests_waiting}\n"
                f"dynamo_worker_kv_usage {ks.gpu_cache_usage_perc}\n")

        status = StatusServer(registry=reg, extra_text_fn=worker_text,
                              slo_fn=mon.payload if mon else None)
        port = await status.start()
        await register_status_endpoint(cp, f"mock-worker-{w}", port)
        workers.append({"engine": eng, "ttft": ttft_h, "tpot": tpot_h,
                        "mon": mon, "status": status})

    ttfts: List[float] = []
    tpots: List[float] = []

    async def one(w: Dict, rec: TraceRecord, uid: int,
                  t_start: float) -> None:
        # Wall pacing to the record's (compressed) arrival time.
        delay = rec.timestamp / 1e3 / speedup_ratio - (
            _time.perf_counter() - t_start)
        if delay > 0:
            await asyncio.sleep(delay)
        toks = tokens_for_record(rec, block_size, unique_seed=uid)
        t0 = _time.perf_counter()
        t_first = None
        t_last = t0
        n = 0
        async for d in w["engine"].generate(PreprocessedRequest(
                request_id=f"r{uid}", model="sla-fleet", token_ids=toks,
                sampling=SamplingParams(max_tokens=rec.output_length))):
            now = _time.perf_counter()
            if d.token_ids and t_first is None:
                t_first = now
            if d.token_ids:
                t_last = now
                n += len(d.token_ids)
            if d.finished:
                break
        if t_first is not None:
            ttft = (t_first - t0) * speedup_ratio
            w["ttft"].observe(ttft)
            ttfts.append(ttft)
            if n > 1:
                tpot = (t_last - t_first) / (n - 1) * speedup_ratio
                w["tpot"].observe(tpot)
                tpots.append(tpot)

    t_start = _time.perf_counter()
    await asyncio.gather(*(
        one(workers[i % num_workers], rec, i, t_start)
        for i, rec in enumerate(records)))
    for w in workers:
        if w["mon"] is not None:
            w["mon"].tick()

    summary = {
        "num_workers": num_workers,
        "requests": len(records),
        "ttft_p50_s": percentile(ttfts, 50),
        "ttft_p99_s": percentile(ttfts, 99),
        "tpot_p50_s": percentile(tpots, 50),
        "tpot_p99_s": percentile(tpots, 99),
    }

    async def teardown() -> None:
        for w in workers:
            await w["engine"].stop()
            await w["status"].stop()
        await cp.close()
        await srv.stop()

    return cp_port, summary, teardown


def fleet_quantiles_from_snapshot(snapshot: Dict) -> Dict:
    """Fleet-aggregate TTFT/TPOT quantiles from a `dynamo_top` snapshot
    (`collect()` dict or `--once --json` output): worst per-worker
    quantile for the p99s (an SLO is only as good as the slowest
    worker), median of per-worker p50s for the centers."""
    rows = [p for p in snapshot.get("processes", [])
            if not p.get("unreachable")
            and p.get("ttft_p50_s") is not None]
    if not rows:
        return {}
    return {
        "workers": len(rows),
        "ttft_p50_s": float(np.median([r["ttft_p50_s"] for r in rows])),
        "ttft_p99_s": max(r["ttft_p99_s"] for r in rows),
        "tpot_p50_s": float(np.median([
            r["tpot_p50_s"] for r in rows
            if r.get("tpot_p50_s") is not None] or [0.0])),
        "tpot_p99_s": max((r["tpot_p99_s"] for r in rows
                           if r.get("tpot_p99_s") is not None),
                          default=0.0),
        "slo_states": sorted({r.get("slo_state") for r in rows
                              if r.get("slo_state")}),
    }


def agreement(modeled_s: float, scraped_s: float,
              factor: float = AGREEMENT_FACTOR,
              atol_s: float = AGREEMENT_ATOL_S) -> bool:
    """The documented modeled-vs-scraped tolerance: within ×`factor`
    either way, OR within `atol_s` absolute.  The factor covers bucket
    quantization (scraped quantiles are FINE_LATENCY_BUCKETS upper
    bounds, ×1.3 spacing) at queueing-dominated latencies; the absolute
    floor covers the overhead-dominated regime — the virtual clock
    charges zero for what the asyncio fleet pays in event-loop
    scheduling, timer slack and queue hops (~ms per step), so
    sub-`atol_s` quantiles can differ by a large *ratio* while agreeing
    to within scheduler noise."""
    if modeled_s < 0 or scraped_s <= 0:
        return False
    if abs(modeled_s - scraped_s) <= atol_s:
        return True
    if modeled_s <= 0:
        return False
    r = scraped_s / modeled_s
    return 1.0 / factor <= r <= factor


@never_engine_thread
def validate_fleet_model(cell: CellConfig, mix: str, rps: float, *,
                         num_workers: int, num_requests: int = 64,
                         block_size: int = 32,
                         slo: Optional[SloTarget] = None,
                         speedup_ratio: float = 1.0,
                         scrape_cli: bool = False) -> Dict:
    """The fleet-scale cross-check: model the cell at `rps` with the
    simulator, run the real mocker fleet under the same trace, scrape
    it through dynamo_top (in-process `collect`, or the actual CLI
    subprocess with `scrape_cli=True`), and report modeled vs scraped
    TTFT/TPOT with the documented agreement verdicts."""
    import asyncio

    fleet_cell = CellConfig(
        name=cell.name, tp=cell.tp, workers=num_workers,
        packed_prefill=cell.packed_prefill, kv_quant=cell.kv_quant,
        spec_decode=cell.spec_decode, disagg=False)
    records = scale_to_rate(
        make_traffic(mix, num_requests, block_size=block_size), rps)
    modeled = simulate_cell(fleet_cell, records, block_size=block_size)

    async def drive() -> Tuple[Dict, Dict]:
        cp_port, summary, teardown = await run_fleet(
            fleet_cell, records, num_workers=num_workers,
            block_size=block_size, slo=slo,
            speedup_ratio=speedup_ratio)
        try:
            if scrape_cli:
                import os
                import subprocess

                out = await asyncio.to_thread(
                    subprocess.run,
                    [sys.executable,
                     os.path.join(os.path.dirname(
                         os.path.dirname(os.path.abspath(__file__))),
                         "tools", "dynamo_top.py"),
                     "--control-plane", f"127.0.0.1:{cp_port}",
                     "--once", "--json"],
                    capture_output=True, timeout=120)
                snapshot = json.loads(out.stdout.decode())
            else:
                sys.path.insert(0, _tools_dir())
                import dynamo_top

                snapshot = await dynamo_top.collect(
                    f"127.0.0.1:{cp_port}")
            return summary, fleet_quantiles_from_snapshot(snapshot)
        finally:
            await teardown()

    summary, scraped = asyncio.run(drive())
    mod = {
        "ttft_p50_s": percentile(modeled.ttft_s, 50),
        "ttft_p99_s": percentile(modeled.ttft_s, 99),
        "tpot_p50_s": percentile(modeled.tpot_s, 50),
        "tpot_p99_s": percentile(modeled.tpot_s, 99),
    }
    return {
        "cell": fleet_cell.to_dict(),
        "mix": mix,
        "offered_rps": rps,
        "modeled": mod,
        "driver": summary,
        "scraped": scraped,
        "ttft_p50_agree": agreement(mod["ttft_p50_s"],
                                    scraped.get("ttft_p50_s", 0.0)),
        "tpot_p50_agree": agreement(mod["tpot_p50_s"],
                                    scraped.get("tpot_p50_s", 0.0)),
        "agreement_factor": AGREEMENT_FACTOR,
    }


def _tools_dir() -> str:
    import os

    return os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools")


# -- sweeps --------------------------------------------------------------


def sweep(cells: Sequence[CellConfig], mixes: Sequence[str],
          loads_rps: Sequence[float], *, num_requests: int = 96,
          block_size: int = 32,
          seed: int = 0) -> Dict[str, List[CellFrontier]]:
    """The full grid: every cell under every mix.  Returns
    {mix: [CellFrontier...]} — capacity planning picks per mix."""
    out: Dict[str, List[CellFrontier]] = {}
    for mix in mixes:
        out[mix] = [profile_cell(c, mix, loads_rps,
                                 num_requests=num_requests,
                                 block_size=block_size, seed=seed)
                    for c in cells]
    return out


SMOKE_LOADS = (4.0, 8.0, 16.0, 32.0, 64.0, 128.0)
SMOKE_SLO = SloTarget(ttft_p99_s=0.25, tpot_p99_s=0.012)
SMOKE_RPS = 40.0
SMOKE_MIX = "agentic"
SMOKE_MOE_MIX = "moe_agentic"


def run_smoke(out_path: Optional[str] = None, *,
              cells: Optional[Sequence[CellConfig]] = None) -> Dict:
    """The deterministic CPU smoke: tiny grids over the mocker cells,
    the pinned capacity fixture (SMOKE_SLO at SMOKE_RPS on the agentic
    mix), and a profile `SlaPlanner` loads unchanged.  Pure virtual
    clock — byte-stable across runs, so tests pin the answer.

    The MoE grid is swept SEPARATELY under the moe_agentic mix and
    answered as its own plan (`moe_plan`): MoE cells never enter the
    dense-model plan, so the original pinned fixture cannot drift from
    this PR — the MoE answer gets its own pin in the gate instead."""
    cells = list(cells or default_cells())
    frontiers = sweep(cells, [SMOKE_MIX], SMOKE_LOADS,
                      num_requests=96)[SMOKE_MIX]
    plan = plan_capacity(frontiers, SMOKE_SLO, SMOKE_RPS)
    profile = build_profile(frontiers, plan=plan,
                            micro_kw={"isl_grid": (128, 256, 512),
                                      "context_grid": (256, 512),
                                      "kv_grid": (0.2, 0.5)})
    moe_frontiers = sweep(moe_cells(), [SMOKE_MOE_MIX], SMOKE_LOADS,
                          num_requests=96)[SMOKE_MOE_MIX]
    moe_plan = plan_capacity(moe_frontiers, SMOKE_SLO, SMOKE_RPS)
    if out_path:
        from dynamo_tpu.planner.interpolation import save_profile

        save_profile(profile, out_path)
    return {"profile": profile, "plan": plan, "frontiers": frontiers,
            "moe_plan": moe_plan, "moe_frontiers": moe_frontiers}


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        "benchmarks.sla_profiler",
        description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="tiny deterministic CPU sweep (mocker cells)")
    p.add_argument("--out", default="sla_profile.json",
                   help="profile output path")
    p.add_argument("--mix", default="agentic", choices=TRAFFIC_MIXES)
    p.add_argument("--mixes", nargs="+", default=None,
                   help="sweep several mixes (default: --mix only)")
    p.add_argument("--ttft-p99", type=float, default=0.25,
                   help="SLO: TTFT p99 target (seconds)")
    p.add_argument("--tpot-p99", type=float, default=0.012,
                   help="SLO: TPOT p99 target (seconds)")
    p.add_argument("--rps", type=float, default=None,
                   help="required offered load (requests/s)")
    p.add_argument("--users", type=float, default=None,
                   help="capacity-plan for this many users "
                        "(with --rph requests/user/hour)")
    p.add_argument("--rph", type=float, default=6.0,
                   help="requests per user per hour (with --users)")
    p.add_argument("--loads", type=float, nargs="+",
                   default=list(SMOKE_LOADS),
                   help="offered-load grid per cell (requests/s)")
    p.add_argument("--requests", type=int, default=96,
                   help="trace length per simulated load point")
    p.add_argument("--fleet", type=int, default=0,
                   help="validate: drive N mocker workers and "
                        "cross-check the model via dynamo_top")
    p.add_argument("--fleet-rps", type=float, default=20.0,
                   help="offered load for the fleet validation run")
    p.add_argument("--speedup", type=float, default=1.0,
                   help="mocker time compression for --fleet")
    p.add_argument("--tpu", action="store_true",
                   help="real-engine frontier via planner.profiler "
                        "cell cores (the BENCH re-baselining vehicle)")
    p.add_argument("--model", default="llama-3-1b",
                   help="model preset for --tpu")
    p.add_argument("--concurrency", type=int, nargs="+",
                   default=[1, 4, 16, 64],
                   help="closed-loop concurrency grid for --tpu")
    args = p.parse_args(argv)

    slo = SloTarget(ttft_p99_s=args.ttft_p99, tpot_p99_s=args.tpot_p99)
    required = args.rps
    if args.users is not None:
        required = args.users * args.rph / 3600.0

    if args.smoke:
        res = run_smoke(args.out)
        plan: CapacityPlan = res["plan"]
        moe_plan: CapacityPlan = res["moe_plan"]
        print(json.dumps({"profile_written": args.out,
                          "cells": len(res["frontiers"]),
                          "plan": plan.to_dict(),
                          "moe_plan": moe_plan.to_dict()}, indent=2))
        return 0 if plan.feasible and moe_plan.feasible else 1

    if args.fleet > 0:
        res = validate_fleet_model(
            CellConfig("base"), args.mix, args.fleet_rps,
            num_workers=args.fleet, slo=slo, scrape_cli=True,
            speedup_ratio=args.speedup)
        print(json.dumps(res, indent=2))
        ok = res["ttft_p50_agree"] and res["tpot_p50_agree"]
        return 0 if ok else 1

    if args.tpu:
        from dynamo_tpu.planner.profiler import cell_core_factory
        from dynamo_tpu.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()

        frontiers = []
        for cell in default_cells():
            if cell.disagg or cell.workers > 1:
                continue   # single-engine sweep; fleet axes are modeled
            make = cell_core_factory(
                args.model, tp=cell.tp, kv_quant=cell.kv_quant,
                spec_decode=cell.spec_decode,
                packed_prefill=cell.packed_prefill or None)
            pts = engine_frontier(make, args.concurrency)
            knee = closed_loop_knee(pts) if len(pts) >= 3 else None
            frontiers.append(CellFrontier(cell=cell, mix="closed-loop",
                                          points=pts, knee_idx=knee))
        plan = (plan_capacity(frontiers, slo, required)
                if required else None)
        profile = build_profile(frontiers, plan=plan)
        from dynamo_tpu.planner.interpolation import save_profile

        save_profile(profile, args.out)
        print(json.dumps({"profile_written": args.out,
                          "plan": plan.to_dict() if plan else None},
                         indent=2))
        return 0

    mixes = args.mixes or [args.mix]
    grid = sweep(default_cells(), mixes, args.loads,
                 num_requests=args.requests)
    plans = {}
    best_mix = mixes[0]
    if required:
        for mix, frontiers in grid.items():
            plans[mix] = plan_capacity(frontiers, slo, required)
    profile = build_profile(grid[best_mix],
                            plan=plans.get(best_mix))
    from dynamo_tpu.planner.interpolation import save_profile

    save_profile(profile, args.out)
    print(json.dumps({
        "profile_written": args.out,
        "plans": {m: pl.to_dict() for m, pl in plans.items()},
    }, indent=2))
    if required and plans and not all(pl.feasible
                                      for pl in plans.values()):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
