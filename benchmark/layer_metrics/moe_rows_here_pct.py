"""Rows the held experts multiply, as a share of ``tokens x experts a
token``: the step counter ``moe_rows_here`` (mean over the layers and
the traced steps). With 16 of 64 experts held and a router that
spreads its load it is 25; what the chip's experts have to do follows
it."""
from benchmark import moe_kinds

LAYER = 'model step under XLA'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = moe_kinds.counters(trace, run)
    if not counted:
        return None
    return 100.0 * counted['moe_rows_here'] / moe_kinds.pairs_per_step(run)

