"""Engine iterations with a request waiting for prefill per iteration that dispatched a chunk: 1.0 when every chance is taken, the duty the controller holds when it skips. Explains TTFT."""

from chipbench import request_readers

LAYER = 'scheduler'
UNIT = 'chances/chunk'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.chances_per_chunk(ctx)
