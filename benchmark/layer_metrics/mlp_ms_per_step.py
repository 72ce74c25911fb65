"""Device time a step under the program's ``mlp`` scope, all phases
(forward, recompute, backward): LN2, the two projections, the GELU and
the residual add (``benchmark/scope_reduce.py``; mean over the
chips)."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.component_ms(trace, run, 'mlp')
