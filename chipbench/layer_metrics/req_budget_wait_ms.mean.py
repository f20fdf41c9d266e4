"""Mean time an admitted request held its slot and pages before its first prefill chunk was planned (request-state clock). Explains TTFT."""

from chipbench import request_readers

LAYER = 'scheduler'
UNIT = 'ms'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.ms_per_entry(ctx, 'budget_wait')
