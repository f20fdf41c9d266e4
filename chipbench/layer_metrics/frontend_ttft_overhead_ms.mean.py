"""What the frontend adds to a first token: its TTFT histogram's mean (from HTTP entry) less the worker's (from its RPC boundary) over the window: preprocess, route and the RPC both ways. Explains TTFT."""

from chipbench import request_readers

LAYER = 'frontend'
UNIT = 'ms'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.frontend_ttft_overhead_ms(ctx)
