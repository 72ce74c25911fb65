"""Device idle time a step under ``trainer.counters_readback`` in the cell
with residual streams, whose step counts FOUR things (the expert layers'
three and ``hc_res_col_sum_err``) that ``fit`` reads back after the loss
(``host_gap_counters_ms.reduce``, as that metric, which lists the cells it
is read in). Nothing where the loop left no such span."""
from benchmark.layer_metrics import host_gap_counters_ms as accepted

LAYER = accepted.LAYER
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return accepted.reduce(trace, run)
