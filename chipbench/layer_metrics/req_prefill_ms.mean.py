"""Mean time from a request's first planned prefill chunk to its last chunk done, the windows its chunks waited behind included (request-state clock). Explains TTFT."""

from chipbench import request_readers

LAYER = 'scheduler'
UNIT = 'ms'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.ms_per_entry(ctx, 'prefill')
