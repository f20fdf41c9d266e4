"""Mean time from a request's first token to the first decode dispatch that holds its row: the ready pool's wait for a merge (request-state clock). Left out by an engine that has no such state."""

from chipbench import request_readers

LAYER = 'EngineCore'
UNIT = 'ms'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.ms_per_entry(ctx, 'cohort_wait')
