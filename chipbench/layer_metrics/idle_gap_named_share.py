"""Share of the listed idle-gap seconds whose label names an engine phase as what the host was doing."""

from chipbench import phase_readers

LAYER = 'device'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return phase_readers.idle_gap_named_share(ctx)
