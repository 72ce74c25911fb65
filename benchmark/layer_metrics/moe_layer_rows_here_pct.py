"""Rows the held routed experts multiply, as a share of ``tokens x
experts a token``: the step counter ``moe_rows_here`` (mean over the
expert layers and the traced steps), as ``moe_rows_here_pct``, which
lists the cells it is read in. With 8 of 128 experts held and a router
that spreads its load it is 6.25."""
from benchmark.layer_metrics import moe_rows_here_pct

LAYER = 'model step under XLA'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_rows_here_pct.reduce(trace, run)
