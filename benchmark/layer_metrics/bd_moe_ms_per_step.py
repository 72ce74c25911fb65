"""Device time a step under the expert layers' three scopes together
(``moe_route`` + ``moe_dispatch`` + ``moe_experts``, all phases;
``mla_kinds.scopes_ms``) in the block-diffusion cell, where a third of
the rows carry one id, the mask's."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, *mla_kinds.ROUTED_SCOPES)
