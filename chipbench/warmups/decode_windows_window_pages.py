"""The greedy decode-window program of an engine whose window layers keep their
pages in a group of their own, once for every (row bucket, page bucket) the
traffic can reach: `decode_windows` with the one argument more that such a
program takes (each row's table of window-group pages, as wide as the other
table)."""

from __future__ import annotations

import time

# Dispatches step programs: its seconds are part of what the harness sums as
# the time to bring every reachable step-program shape up.
STEP_PROGRAMS = True


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
    widths = [w for w in sched.page_bucket_ladder() if w <= top]
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
            # The program's last argument: each row's window-group table,
            # every entry the null block.
            out = fn(core.params, core.cache, i32, pos, i32, bts, f32,
                     i32, f32 + 1.0, keys, i32, bts)
            core.cache = out[0]
            done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}
