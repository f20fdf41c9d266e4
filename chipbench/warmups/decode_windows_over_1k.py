"""The greedy decode-window program, once for every (row bucket, page bucket)
that traffic whose prompts are all MIN_CONTEXT tokens or longer can reach: the
sibling of `decode_windows.py` for long-context mixes, where a decoding row
never has a short block table and the page buckets under MIN_CONTEXT would be
compiled for nothing (4 of this ladder's 8 at 13,312 tokens of context: 28
programs, about 150 s of a first start).  A shorter request (the harness's
HTTP probes, a comparison's short prompts) still runs: its shape is built when
it is first dispatched, before the window."""

from __future__ import annotations

import time

# Dispatches step programs: its seconds are part of what the harness sums as
# the time to bring every reachable step-program shape up.
STEP_PROGRAMS = True
# The shortest prompt of the mixes whose configurations name this warm-up
# (`traffic/long-context.json`: input_tokens.min): a decoding row's context
# is longer than this.
MIN_CONTEXT = 1024


def warm(core, max_context: int, vocab: int) -> dict:
    """Dispatch the greedy decode-window program once for every (row bucket,
    page bucket) the traffic can reach, all rows dead (context 0)."""
    import jax

    t0 = time.monotonic()
    done = 0
    sched = core.scheduler.config
    k = core.config.decode_window
    if k <= 1:
        return {"shapes": 0, "seconds": 0.0}
    lag = core.config.window_pipeline_depth
    top = sched.bucket_for_pages(
        -(-(max_context + (lag + 1) * k) // core.block_size))
    low = sched.bucket_for_pages(-(-(MIN_CONTEXT + 1) // core.block_size))
    widths = [w for w in sched.page_bucket_ladder() if low <= w <= top]
    rows = sorted({sched.bucket_for_decode(n)
                   for n in range(1, sched.max_seqs + 1)})
    fn = core._window_fn(True)
    for b in rows:
        i32 = jax.numpy.zeros((b,), jax.numpy.int32)
        f32 = jax.numpy.zeros((b,), jax.numpy.float32)
        pos = jax.numpy.full((b,), core._pad_position, jax.numpy.int32)
        keys = jax.numpy.zeros((b, 2), jax.numpy.uint32)
        for w in widths:
            if not core.counters.note_dispatch("window", True, b, w):
                continue
            bts = jax.numpy.zeros((b, w), jax.numpy.int32)
            out = fn(core.params, core.cache, i32, pos, i32, bts, f32,
                     i32, f32 + 1.0, keys, i32)
            core.cache = out[0]
            done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}
