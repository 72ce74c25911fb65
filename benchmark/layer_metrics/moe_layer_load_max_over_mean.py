"""The largest load of a held routed expert over the mean load of the
held ones (the step counters ``moe_load_max`` / ``moe_load_mean``), as
``moe_load_max_over_mean``, which lists the cells it is read in."""
from benchmark.layer_metrics import moe_load_max_over_mean

LAYER = 'model step under XLA'
UNIT = 'ratio'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_load_max_over_mean.reduce(trace, run)
