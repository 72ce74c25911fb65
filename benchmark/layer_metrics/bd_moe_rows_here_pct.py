"""Rows the held experts multiply, as a share of ``rows x experts a
row`` with BOTH copies' rows counted (the step counter ``moe_rows_here``,
mean over the layers and the traced steps; ``bd_kinds.pairs_per_step``).
With 16 of 128 experts held and a router that spreads its load it is
12.5."""
from benchmark import bd_kinds

LAYER = 'model step under XLA'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = bd_kinds.counters(trace, run)
    if not counted:
        return None
    return 100.0 * counted['moe_rows_here'] / bd_kinds.pairs_per_step(run)
