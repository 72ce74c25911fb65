"""Device time a step under the program's ``hc`` scope, all phases: the
residual streams' connections, two a layer (``mla_kinds.scopes_ms``): the
coefficients and the three mixes, beside ``attention`` and ``mlp`` and not
inside them. Nothing on a program without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'hc')
