"""95th percentile of the gaps between successive output tokens at the client, pooled.  Tokens arrive eight at a time (one decode window), so seven gaps in eight are near zero and this reads the 60th percentile of the window intervals; recorded, not judged, because a change to how tokens are grouped would move the percentile across that edge."""

from chipbench import readers

LAYER = 'end to end (recorded, not judged)'
UNIT = 'ms'
SOURCE = 'host_clock'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.itl_ms(ctx, 95)
