"""How far the stream mix is from doubly stochastic after the last
Sinkhorn-Knopp round: the step counter ``hc_res_col_sum_err``, the mean
over layers, sublayers and tokens of ``max_j |sum_i H_res[i, j] - 1|``
(rows sum to 1 after any round; the columns as far as the rounds have
converged at the step's weights), mean over the traced steps
(``hc_kinds.counters``)."""
from benchmark import hc_kinds

LAYER = 'model step under XLA'
UNIT = 'abs'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    counted = hc_kinds.counters(trace, run)
    return None if not counted else counted[hc_kinds.COUNTER]
