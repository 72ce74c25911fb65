"""Device time a step under the program's ``hc_coeff`` scope (inside
``hc``), all phases: the connections' coefficients, the norm over a token's
streams, the ``phi`` product and the Sinkhorn-Knopp rounds with their
backward (``mla_kinds.scopes_ms``). Nothing on a program without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'hc_coeff')
