"""Every packed-prefill shape of an engine whose window layers keep their pages
in a group of their own, once: `packed_prefill` with the one argument more
that such a program takes (each segment's table of window-group pages)."""

from __future__ import annotations

import time

# Dispatches step programs: its seconds are part of what the harness sums as
# the time to bring every reachable step-program shape up.
STEP_PROGRAMS = True


def warm(core, max_context: int, vocab: int) -> dict:
    """Dispatch every packed-prefill shape once, all segments empty: what
    the program's `--prewarm-prefill` does, done here with the other two
    sets so that one place counts and times all of them."""
    import jax

    t0 = time.monotonic()
    done = 0
    if not core._use_packed_prefill:
        return {"shapes": 0, "seconds": 0.0}
    fn = core._packed_prefill_fn()
    for (t, r, p) in core.packed_prefill_shape_set():
        if not core.counters.note_dispatch("prefill_packed", t, r, p):
            continue
        zt = jax.numpy.zeros((t,), jax.numpy.int32)
        zr = jax.numpy.zeros((r,), jax.numpy.int32)
        pos = jax.numpy.full((t,), core._pad_position, jax.numpy.int32)
        # The program's last argument: each segment's window-group table,
        # every entry the null block.
        bts = jax.numpy.zeros((r, p), jax.numpy.int32)
        out = fn(core.params, core.cache, zt, pos, zt, bts, zr, zr, zr, zr,
                 bts)
        core.cache = out[1]
        done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}
