"""Device time a step in the backward pass proper: the operations whose
name carries ``transpose(...)``, less the recomputation
(``recompute_ms_per_step``). Across chips the gradient all-reduces fall
here: XLA names them after the backward operation they reduce
(``benchmark/scope_reduce.py``; mean over the chips)."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.phase_ms(trace, run, 'backward')
