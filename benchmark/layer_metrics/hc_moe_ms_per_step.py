"""Device time a step under the expert layers' four scopes together
(``moe_route`` + ``moe_dispatch`` + ``moe_experts`` + ``moe_shared``, all
phases; ``mla_kinds.scopes_ms``) in the cell with residual streams: 4 of 64
experts a token, 8 held, and the always-on expert."""
from benchmark import hc_kinds, mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, *hc_kinds.MOE_SCOPES)
