"""Device time a step under the program's ``mla_latent`` scope, all
phases, in the cell with residual streams: latent attention's projections,
here with q's down-projection, its norm and its up-projection among them,
the latent's norm and the split (``mla_kinds.scopes_ms``, as
``mla_latent_ms_per_step``). Nothing on a program without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'mla_latent')
