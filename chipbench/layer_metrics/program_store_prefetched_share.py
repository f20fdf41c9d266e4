"""Percent of the step programs served from the program store before the window that its read-ahead had loaded, or was loading, when their first call came: prefetched over hits at the window's first scrape; 0 where the store served none (a start that misses it finds nothing to read ahead)."""

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'setup_s'

_SERIES = 'dynamo_worker_program_store_%s_total'


def read(ctx):
    page = (ctx.scrapes.get('window_start') or {}).get('worker') or {}
    hits, misses, ahead = (page.get(_SERIES % what)
                           for what in ('hits', 'misses', 'prefetched'))
    if hits is None or misses is None or ahead is None or hits + misses <= 0:
        return None
    return 100.0 * ahead / hits if hits > 0 else 0.0
