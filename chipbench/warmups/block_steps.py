"""The greedy block program of a block-diffusion engine (denoising forwards
and the commit, one device call), once for every (row bucket, page bucket)
the traffic can reach."""

from __future__ import annotations

import time

# Dispatches step programs: its seconds are part of what the harness sums as
# the time to bring every reachable step-program shape up.
STEP_PROGRAMS = True


def warm(core, max_context: int, vocab: int) -> dict:
    """Dispatch the greedy block program once for every (row bucket, page
    bucket), all rows dead (block end 0, positions on the null block): the
    call runs no denoising forward (nothing live is masked) and one commit,
    and compiles the whole program."""
    import jax

    t0 = time.monotonic()
    done = 0
    if not core._diffusion:
        raise RuntimeError("the engine generates no blocks: this warm-up is "
                           "for a block-diffusion model's step family")
    block = core.config.model.diffusion_block_length
    sched = core.scheduler.config
    top = sched.bucket_for_pages(
        -(-(max_context + block) // core.block_size))
    widths = [w for w in sched.page_bucket_ladder() if w <= top]
    rows = sorted({sched.bucket_for_decode(n)
                   for n in range(1, sched.max_seqs + 1)})
    fn = core._block_fn(True)
    np = jax.numpy
    for b in rows:
        i32 = np.zeros((b,), np.int32)
        f32 = np.zeros((b,), np.float32)
        tok = np.zeros((b, block), np.int32)
        pos = np.full((b, block), core._pad_position, np.int32)
        keys = np.zeros((b, 2), np.uint32)
        for w in widths:
            if not core.counters.note_dispatch("block", True, False, b, w):
                continue
            out = fn(core.params, core.cache, tok, pos, i32,
                     np.zeros((b, w), np.int32), f32, i32, f32 + 1.0, keys,
                     i32)
            core.cache = out[0]
            done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}
