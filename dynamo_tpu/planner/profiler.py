"""Pre-deployment mini-profiler: sweep OUR engine, emit the SLA profile.

Role of the reference's `benchmarks/profiler/profile_sla.py` (genai-perf
sweeps of TTFT/ITL over TP x load feeding `perf_interpolation.py`): run
the real EngineCore across an ISL grid (prefill) and a context x
kv-load grid (decode), measure TTFT/ITL/throughput per chip, and write
the profile planner/interpolation.py consumes.

Chip-granular and engine-native: no HTTP in the loop, the engine is
driven synchronously, one `step()` after another, so the profile
measures the serving step itself.  Works against any model preset on TPU or the
CPU test backend (tiny grids for CI).
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional, Sequence

import numpy as np

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig

logger = logging.getLogger(__name__)


def profile_engine(
    make_core,
    isl_grid: Sequence[int] = (128, 256, 512),
    context_grid: Sequence[int] = (256, 512, 1024),
    kv_grid: Sequence[float] = (0.2, 0.5, 0.8),
    decode_tokens: int = 32,
) -> Dict:
    """Sweep a fresh EngineCore per cell; returns the profile dict.

    `make_core() -> EngineCore` builds one engine per cell (with DISTINCT
    prompts per attempt so measurements never prefix-hit each other).
    Every cell runs its workload twice on the SAME core and keeps the
    SECOND measurement: the first run pays the cell's XLA compiles, and
    a compile-polluted TTFT would poison every interpolation built on it.
    """
    prefill = {"isl": [], "ttft_s": [], "tok_s_per_chip": []}
    for isl in isl_grid:
        core = make_core()
        vocab = core.config.model.vocab_size
        ttft = 0.0
        for attempt in range(2):  # warm, then measure
            rng = np.random.default_rng(isl * 7 + attempt)
            prompt = rng.integers(1, vocab, size=isl).tolist()
            core.add_request(f"p{attempt}", prompt,
                             SamplingParams(max_tokens=1))
            t0 = time.perf_counter()
            done = False
            while not done:
                for d in core.step():
                    if d.token_ids or d.finished:
                        done = True
            ttft = time.perf_counter() - t0
            while core.has_work:
                core.step()  # drain the terminal delta
        prefill["isl"].append(int(isl))
        prefill["ttft_s"].append(ttft)
        prefill["tok_s_per_chip"].append(isl / ttft if ttft > 0 else 0.0)
        logger.info("profile prefill isl=%d ttft=%.3fs", isl, ttft)

    decode = {"kv_usage": list(map(float, kv_grid)),
              "context": [int(c) for c in context_grid],
              "itl_s": [], "tok_s_per_chip": []}
    for ctx in context_grid:
        itl_row, thpt_row = [], []
        for kv in kv_grid:
            core = make_core()
            cfg = core.config
            bs = core.block_size
            vocab = cfg.model.vocab_size
            pages_per_seq = (ctx + bs - 1) // bs + 1
            usable = cfg.num_blocks - 1
            batch = max(1, int(kv * usable / pages_per_seq))
            batch = min(batch, cfg.scheduler.max_seqs)
            itl = wall = produced = 0
            for attempt in range(2):  # warm, then measure
                rng = np.random.default_rng(
                    int(ctx * 1000 + kv * 100 + attempt))
                for i in range(batch):
                    core.add_request(
                        f"d{attempt}-{i}",
                        rng.integers(1, vocab, size=ctx).tolist(),
                        SamplingParams(max_tokens=decode_tokens))
                # Prefill everything first (excluded from the ITL window).
                while core.has_pending_prefill:
                    core.step()
                produced = 0
                t0 = time.perf_counter()
                while core.has_work:
                    produced += sum(len(d.token_ids) for d in core.step())
                wall = time.perf_counter() - t0
                itl = wall / max(produced / batch, 1.0)
            itl_row.append(itl)
            thpt_row.append(produced / wall if wall > 0 else 0.0)
            logger.info("profile decode ctx=%d kv=%.2f itl=%.4fs "
                        "thpt=%.1f", ctx, kv, itl, thpt_row[-1])
        decode["itl_s"].append(itl_row)
        decode["tok_s_per_chip"].append(thpt_row)
    return {"prefill": prefill, "decode": decode}


def default_core_factory(model: str = "llama-3-1b",
                         num_blocks: int = 2048,
                         block_size: int = 64,
                         decode_window: int = 8,
                         max_seqs: int = 64):
    """EngineCore factory matching the serving geometry."""
    return cell_core_factory(model, num_blocks=num_blocks,
                             block_size=block_size,
                             decode_window=decode_window,
                             max_seqs=max_seqs)


def cell_core_factory(model: str = "llama-3-1b", *,
                      num_blocks: int = 2048,
                      block_size: int = 64,
                      decode_window: int = 8,
                      max_seqs: int = 64,
                      tp: int = 1,
                      kv_quant: str = "none",
                      spec_decode: int = 0,
                      packed_prefill: Optional[bool] = None):
    """EngineCore factory over the serving feature axes PRs 6-10
    shipped — the real-engine half of one sweep cell
    (benchmarks/sla_profiler.py drives this on TPU; the mocker cells
    cover CPU CI).  `tp > 1` builds a tensor-parallel mesh the same way
    the worker's `--tp` flag does."""

    from dynamo_tpu.models.loader import resolve_model

    cfg, params, _, _ = resolve_model(model)

    def make():
        mesh = None
        if tp > 1:
            import jax

            from dynamo_tpu.parallel import MeshConfig, make_mesh
            cfg_m = MeshConfig(tp=tp)
            mesh = make_mesh(cfg_m, jax.devices()[:cfg_m.size])
        return EngineCore(EngineConfig(
            model=cfg, num_blocks=num_blocks,
            mesh=mesh,
            enable_prefix_cache=False,
            decode_window=decode_window,
            kv_quant=kv_quant,
            speculative_tokens=spec_decode,
            packed_prefill=packed_prefill,
            scheduler=SchedulerConfig(
                max_seqs=max_seqs, block_size=block_size)),
            params=params)

    return make


def main(argv: Optional[list] = None) -> None:
    import argparse

    from dynamo_tpu.planner.interpolation import save_profile

    p = argparse.ArgumentParser("dynamo_tpu.planner.profiler")
    p.add_argument("--model", default="llama-3-1b")
    p.add_argument("--out", default="sla_profile.json")
    p.add_argument("--isl", type=int, nargs="+", default=[128, 256, 512])
    p.add_argument("--context", type=int, nargs="+",
                   default=[256, 512, 1024])
    p.add_argument("--kv", type=float, nargs="+", default=[0.2, 0.5, 0.8])
    p.add_argument("--num-blocks", type=int, default=2048)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    profile = profile_engine(
        default_core_factory(args.model, num_blocks=args.num_blocks),
        isl_grid=args.isl, context_grid=args.context, kv_grid=args.kv)
    save_profile(profile, args.out)
    print(f"profile written to {args.out}")


if __name__ == "__main__":
    main()
