"""Tokens a live row emits per forward it rides: decode tokens emitted over rows x forwards (diffusion_row_forwards_total). 0.8 for full blocks of 4 in 5 forwards; the causal engine's is 1."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = 'tokens/forward'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    toks = ctx.delta('worker', 'dynamo_worker_engine_decode_tokens_emitted')
    rows = block_readers.tally(ctx, 'diffusion_row_forwards')
    if toks is None or not rows:
        return None
    return toks / rows
