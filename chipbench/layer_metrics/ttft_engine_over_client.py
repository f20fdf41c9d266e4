"""Mean time from arrival at the engine to first token (request-state clock) over the client's mean TTFT: what is left to 1.0 is the frontend, the wire and the client. Explains TTFT."""

from chipbench import request_readers

LAYER = 'EngineCore'
UNIT = 'x'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.ttft_inside_over_client(ctx)
