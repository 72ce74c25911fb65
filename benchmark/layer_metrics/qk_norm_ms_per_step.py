"""Device time a step under the program's ``qk_norm`` scope (inside
``attention``), all phases: the RMSNorm over every q head and every k
head between the projection and the flash kernels, under XLA
(``mla_kinds.scopes_ms``; mean over the chips). Nothing on a program
without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'qk_norm')
