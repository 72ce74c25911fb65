"""Largest over mean load of a held expert, in rows (the step counters
``moe_load_max`` and ``moe_load_mean``, means over the layers and the
traced steps): 1 is a router that spreads its load evenly, the number
of held experts one that sends everything to one of them."""
from benchmark import moe_kinds

LAYER = 'model step under XLA'
UNIT = 'ratio'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = moe_kinds.counters(trace, run)
    if not counted or not counted['moe_load_mean']:
        return None
    return counted['moe_load_max'] / counted['moe_load_mean']

