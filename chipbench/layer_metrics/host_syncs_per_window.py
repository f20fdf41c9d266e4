"""Blocking device-to-host reads per decode-window dispatch (engine counters)."""

from chipbench import readers

LAYER = 'EngineCore'
UNIT = 'syncs/window'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.host_syncs_per_window(ctx)
