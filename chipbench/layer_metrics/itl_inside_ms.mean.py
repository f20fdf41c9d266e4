"""The judged inter-token latency as the engine sees it: request-seconds from first token to finish over the tokens emitted after a first one."""

from chipbench import request_readers

LAYER = 'EngineCore'
UNIT = 'ms'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.itl_inside_ms(ctx)
