"""Share of their roofline the grouped full-causal flash calls
(``flash_fwd``, ``flash_dq``, ``flash_dkv`` on 32 query heads over 4 kv
heads) reach together: half the score square under the causal mask, k
and v read once a group (``moe_kinds.gqa_call_cost``)."""
from benchmark import moe_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_kinds.gqa_roofline_pct(trace, run, 'global')
