"""Device time a step under the program's ``moe_shared`` scope, all
phases: the always-on expert beside the routed ones
(``mla_kinds.scopes_ms``). Nothing on a program without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'moe_shared')
