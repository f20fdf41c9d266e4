"""Share of the request-seconds from first token to finish spent waiting for the first decode dispatch."""

from chipbench import request_readers

LAYER = 'EngineCore'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.cohort_wait_share(ctx)
