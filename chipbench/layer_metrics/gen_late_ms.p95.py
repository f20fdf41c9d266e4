"""How late the generator sent: send time minus due time, 95th percentile. A starved generator is not a fast server."""

from chipbench import readers

LAYER = 'load generator + HTTP'
UNIT = 'ms'
SOURCE = 'host_clock'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.gen_late_ms(ctx, 95)
