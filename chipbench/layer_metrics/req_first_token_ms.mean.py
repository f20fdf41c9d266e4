"""Mean time from a request's last prefill chunk done to its first token appended: the sample's fetch and settle (request-state clock). Explains TTFT."""

from chipbench import request_readers

LAYER = 'EngineCore'
UNIT = 'ms'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.ms_per_entry(ctx, 'first_token')
