"""(token, expert) assignments computed over distinct experts touched, in a causal engine (prefill chunks, decode window steps and single steps): the rows an expert's weights are streamed for.  The block engine's reader under a name of its own: that metric's list of cells is held to its one cell by the accepted benchmark's tests."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = 'rows/expert'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return block_readers.ratio(ctx, 'moe_assignments', 'moe_experts_touched')
