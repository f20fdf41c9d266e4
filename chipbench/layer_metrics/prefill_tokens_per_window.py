"""Prompt tokens handed to prefill programs per decode window dispatched (engine counters)."""

from chipbench import phase_readers

LAYER = 'scheduler'
UNIT = 'tokens/window'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return phase_readers.prefill_tokens_per_window(ctx)
