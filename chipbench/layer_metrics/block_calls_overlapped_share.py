"""Percent of block program calls dispatched while the call before was still unread (block_calls_overlapped_total over diffusion_forwards_total{kind=commit}, one commit being one block call): the host's work between two calls runs beside the device's, not before it. 0 for an engine that reads each call before it builds the next."""

from chipbench import block_readers

LAYER = 'EngineCore'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    overlapped = block_readers.tally(ctx, 'block_calls_overlapped')
    fw = block_readers.forwards(ctx)
    if overlapped is None or fw is None:
        return None
    return 100.0 * overlapped / fw[1]
