"""Share of their roofline the grouped causal-band flash calls
(``flash_fwd_band``, ``flash_dq_band``, ``flash_dkv_band``: a query sees
the ``sliding_window`` keys up to itself) reach together; k and v read
once a group (``moe_kinds.gqa_call_cost``)."""
from benchmark import moe_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return moe_kinds.gqa_roofline_pct(trace, run, 'window')
