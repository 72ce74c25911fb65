"""Share of its roofline the expert layer's grouped products reach: the
least time the chip could take for what the LIVE rows of the traced
steps need (the step counter ``moe_rows_here``; forward and twice that
backward, ``moe_kinds.experts_cost``) over the time under the
``moe_experts`` scope. The ``say`` line names the bound."""
from benchmark import moe_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_kinds.experts_roofline_pct(trace, run)
