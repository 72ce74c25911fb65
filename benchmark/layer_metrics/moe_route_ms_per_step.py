"""Device time a step under the program's ``moe_route`` scope, all
phases: the router, top-k, the order of the held pairs by expert and
the counts (``benchmark/moe_kinds.py``; mean over the chips). Nothing on
a program without the scope."""
from benchmark import moe_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_kinds.scope_ms(trace, run, 'moe_route')
