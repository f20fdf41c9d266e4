"""The engine's own inter-token latency over the client's pooled one: what is left to 1.0 is delivery, wire and HTTP; over 1.0 a state is counted twice."""

from chipbench import request_readers

LAYER = 'EngineCore'
UNIT = 'x'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.itl_inside_over_client(ctx)
