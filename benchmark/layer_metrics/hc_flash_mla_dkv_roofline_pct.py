"""Share of its roofline the latent-attention dkv call
(``flash_dkv_mla``: the scores again, dV and dP at 128 lanes, dK at 192)
reaches in the cell with residual streams: 4096 keys, the causal half of
the score square, a softmax scale of the caller's
(``mla_kinds.roofline_pct``, as ``flash_mla_dkv_roofline_pct``, which
lists the cells it is read in). Nothing where the step has no such call."""
from benchmark import mla_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.roofline_pct(trace, run, 'flash_dkv_mla')
