"""Largest over mean load of a held expert, in rows (the step counters
``moe_load_max`` and ``moe_load_mean``), in the block-diffusion cell: what
the hazard of one id on a third of the rows is watched by (1 is an even
load, 16 everything on one held expert)."""
from benchmark import bd_kinds

LAYER = 'model step under XLA'
UNIT = 'ratio'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = bd_kinds.counters(trace, run)
    if not counted or not counted['moe_load_mean']:
        return None
    return counted['moe_load_max'] / counted['moe_load_mean']
