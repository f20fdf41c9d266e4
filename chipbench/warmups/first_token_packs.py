"""The first-token sampling of 1 .. n prompts that finish prefill in one pack,
through the engine's public entry."""

from __future__ import annotations

import time

# Drives the engine's public entry, not step programs shape by shape: its
# seconds are not part of the step-program sum.
STEP_PROGRAMS = False


def warm(core, max_context: int, vocab: int) -> dict:
    """n prompts that finish prefill in one pack sample n first tokens in
    one call: run n = 1 .. the pack's segment count through the engine's
    public add_request / step, one token each."""
    from dynamo_tpu.engine.sampling import SamplingParams

    t0 = time.monotonic()
    top = core.scheduler.config.packed_prefill_segments
    for n in range(1, top + 1):
        for i in range(n):
            core.add_request(f"chipbench-warm-{n}-{i}",
                             [1 + (7 * n + i) % (vocab - 1)] * 5,
                             SamplingParams(max_tokens=1))
        while core.has_work:
            core.step()
    return {"packs": top, "seconds": time.monotonic() - t0}
