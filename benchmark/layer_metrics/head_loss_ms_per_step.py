"""Device time a step under the program's ``head_loss`` scope, all phases:
the final LayerNorm, the (tied) head's matmul, the f32 logits, the
cross-entropy and its mean (``benchmark/scope_reduce.py``; mean over the
chips)."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.component_ms(trace, run, 'head_loss')
