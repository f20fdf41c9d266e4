"""The fused greedy single decode step of an engine whose window layers keep
their pages in a group of their own, once for every (row bucket, page bucket)
the traffic can reach: `greedy_single_steps` with the one argument more that
such a program takes (each row's table of window-group pages)."""

from __future__ import annotations

import time

# Dispatches step programs: its seconds are part of what the harness sums as
# the time to bring every reachable step-program shape up.
STEP_PROGRAMS = True


def warm(core, max_context: int, vocab: int) -> dict:
    """The fused greedy single decode step, for every (row bucket, page
    bucket): the engine takes it whenever every decoding request has just
    left prefill (none is in the window cohort yet), and whenever every
    one of them has less than half a window left to generate (the engine's
    end-of-life guard), which most requests of unaligned length reach."""
    import jax

    t0 = time.monotonic()
    done = 0
    if not core._fused_greedy_capable:
        raise RuntimeError("the engine has no fused greedy single step: its "
                           "single-step shapes cannot be warmed from here")
    sched = core.scheduler.config
    top = sched.bucket_for_pages(-(-max_context // core.block_size))
    widths = [w for w in sched.page_bucket_ladder() if w <= top]
    rows = sorted({sched.bucket_for_decode(n)
                   for n in range(1, sched.max_seqs + 1)})
    fn = core._greedy_step_fn()
    for b in rows:
        i32 = jax.numpy.zeros((b,), jax.numpy.int32)
        tok = jax.numpy.zeros((b, 1), jax.numpy.int32)
        pos = jax.numpy.full((b, 1), core._pad_position, jax.numpy.int32)
        for w in widths:
            if not core.counters.note_dispatch("decode1g", b, w):
                continue
            # The program's last argument: each row's window-group table,
            # every entry the null block.
            bts = jax.numpy.zeros((b, w), jax.numpy.int32)
            out = fn(core.params, core.cache, tok, pos, i32, bts, i32, bts)
            core.cache = out[1]
            done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}
