"""Share of the step's ``2 b L`` rows (the noised copy and the clean
one) whose id is the mask's: the step counter ``bd_mask_rows``, mean
over the traced steps. Half the mean noise level: 35 at ``t`` uniform on
[0.45, 0.95)."""
from benchmark import bd_kinds

LAYER = 'model step under XLA'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = bd_kinds.counters(trace, run)
    return None if not counted else 100.0 * counted['bd_mask_rows']
