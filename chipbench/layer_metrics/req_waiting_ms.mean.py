"""Mean time a request stood in the scheduler's queue before admission: no slot, no pages, or held by QoS (request-state clock). Explains TTFT."""

from chipbench import request_readers

LAYER = 'scheduler'
UNIT = 'ms'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.ms_per_entry(ctx, 'waiting')
