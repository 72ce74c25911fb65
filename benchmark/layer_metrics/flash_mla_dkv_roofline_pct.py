"""Share of its roofline the latent-attention ``flash_dkv_mla`` call (the
scores and dP again, dK_nope, dV and the shared rotary key's gradient)
reaches: the larger of its FLOPs over peak FLOP/s and its bytes over
peak bytes/s, from shapes (``mla_kinds.call_cost``), over its time in
the trace."""
from benchmark import mla_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.roofline_pct(trace, run, 'flash_dkv_mla')
