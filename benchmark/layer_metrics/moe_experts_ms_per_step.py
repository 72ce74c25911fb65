"""Device time a step under the program's ``moe_experts`` scope, all
phases: the grouped products ``moe_gmm``, ``moe_gmm_dx``, ``moe_gmm_dw``
over the held rows and the activation between them
(``benchmark/moe_kinds.py``; mean over the chips)."""
from benchmark import moe_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_kinds.scope_ms(trace, run, 'moe_experts')
