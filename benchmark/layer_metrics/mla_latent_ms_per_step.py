"""Device time a step under the program's ``mla_latent`` scope, all
phases: latent attention's three projections, the latent's norm and the
split, everything of attention but the flash kernels and the output
projection (``mla_kinds.scopes_ms``). Nothing on a program without the
scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'mla_latent')
