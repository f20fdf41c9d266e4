"""(token, expert) assignments computed over distinct experts touched: the rows an expert's weights are streamed for."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = 'rows/expert'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return block_readers.ratio(ctx, 'moe_assignments', 'moe_experts_touched')
