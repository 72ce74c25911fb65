"""Device time a step under the expert layers' four scopes together
(``moe_route`` + ``moe_dispatch`` + ``moe_experts`` + ``moe_shared``),
all phases: a single-mixer expert layer but for its norm and residual
(``mla_kinds.scopes_ms``). Nothing on a program without the scopes."""
from benchmark import mla_kinds

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.scopes_ms(trace, run, *mla_kinds.ROUTED_SCOPES,
                               'moe_shared')
