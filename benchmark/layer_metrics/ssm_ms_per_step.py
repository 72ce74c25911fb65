"""Device time a step under the program's ``ssm`` scope, all phases: the
Mamba-2 layers whole (norm, projections, conv, the scan's kernels, the
gate norm, the residual), so that ``attention_`` + ``mlp_`` + ``ssm_`` +
``head_loss_ms_per_step`` account for a step of single-mixer layers
(``mla_kinds.scopes_ms``). Nothing on a program without the scope."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, 'ssm')
