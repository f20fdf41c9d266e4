"""Percent of the block programs' forwards that are commits (they decide nothing: one a block call, to write the final K and V)."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    fw = block_readers.forwards(ctx)
    return None if fw is None else 100.0 * fw[1] / (fw[0] + fw[1])
