"""Decode tokens emitted per decode step: mean live rows of a step."""

from chipbench import readers

LAYER = 'scheduler'
UNIT = 'rows'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.batch_rows(ctx)
