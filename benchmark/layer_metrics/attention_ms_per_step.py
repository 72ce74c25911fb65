"""Device time a step under the program's ``attention`` scope, all phases
(forward, recompute, backward): LN1, the qkv and output projections,
the attention itself (the flash kernels where they run) and the
residual add (``benchmark/scope_reduce.py``; mean over the chips)."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.component_ms(trace, run, 'attention')
