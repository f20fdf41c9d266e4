"""Percent of the step programs built before the window that the program store held: hits over hits plus misses at the window's first scrape."""

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'setup_s'

_SERIES = 'dynamo_worker_program_store_%s_total'


def read(ctx):
    page = (ctx.scrapes.get('window_start') or {}).get('worker') or {}
    hits, misses = page.get(_SERIES % 'hits'), page.get(_SERIES % 'misses')
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
