"""Device time a step under the program's ``moe_dispatch`` scope, all
phases: the gather of the held rows and the scatter-add of the experts'
rows back onto their tokens, with their weights
(``benchmark/moe_kinds.py``; mean over the chips)."""
from benchmark import moe_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_kinds.scope_ms(trace, run, 'moe_dispatch')
