"""Rows the held experts multiply, as a share of ``tokens x experts a
token`` (the step counter ``moe_rows_here``, mean over the expert layers
and the traced steps; ``hc_kinds.counters``). With 8 of 64 experts held
and a router that spreads its load it is 12.5."""
from benchmark import hc_kinds, moe_kinds

LAYER = 'model step under XLA'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = hc_kinds.counters(trace, run)
    if not counted:
        return None
    return 100.0 * counted['moe_rows_here'] / moe_kinds.pairs_per_step(run)
