"""Device time a step in the loss's first forward pass: the operations
whose name carries a ``jvp(...)`` wrapper and no ``transpose(...)``
(``benchmark/scope_reduce.py``; mean over the chips). Reads JAX's own
wrappers, so it survives an executable without the program's names."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.phase_ms(trace, run, 'forward')
