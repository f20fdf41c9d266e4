"""Percent of the rows block program calls computed whose block was dropped at the read (diffusion_rows_dropped_total over dropped + diffusion_blocks_committed_total): a sequence that stopped or was cancelled while its next block was already in flight. Work added by reading one call behind; 0 where sequences end on max_tokens."""

from chipbench import block_readers

LAYER = 'EngineCore'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    dropped = block_readers.tally(ctx, 'diffusion_rows_dropped')
    committed = block_readers.tally(ctx, 'diffusion_blocks_committed')
    if dropped is None or committed is None or not dropped + committed:
        return None
    return 100.0 * dropped / (dropped + committed)
