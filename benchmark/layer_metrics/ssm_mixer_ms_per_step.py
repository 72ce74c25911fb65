"""Device time a step under the program's ``ssm_mixer`` scope, all
phases: a Mamba-2 layer's two projections, the conv and the gate norm,
everything of the mixer outside the scan's kernels
(``mla_kinds.scopes_ms``). Nothing on a program without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'ssm_mixer')
