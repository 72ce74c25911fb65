"""Largest over mean load of a held expert, in rows (the step counters
``moe_load_max`` and ``moe_load_mean``; ``hc_kinds.counters``), in the cell
with residual streams: 1 is an even load, 8 everything on one held
expert."""
from benchmark import hc_kinds

LAYER = 'model step under XLA'
UNIT = 'ratio'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = hc_kinds.counters(trace, run)
    if not counted or not counted['moe_load_mean']:
        return None
    return counted['moe_load_max'] / counted['moe_load_mean']
