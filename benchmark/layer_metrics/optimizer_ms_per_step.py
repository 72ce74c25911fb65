"""Device time a step under the program's ``optimizer`` scope: the optax
update and the parameter update, outside the gradient
(``benchmark/scope_reduce.py``; mean over the chips). ``None`` where the
compiled step carries none of the program's names."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.phase_ms(trace, run, 'optimizer')
