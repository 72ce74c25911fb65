"""Device time a step under the program's ``hc_mix`` scope (inside
``hc``), all phases: what a sublayer reads of the streams, what it writes
back and the streams' own mix, the passes over ``[b, s, streams * dim]``
(``mla_kinds.scopes_ms``). Nothing on a program without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'hc_mix')
