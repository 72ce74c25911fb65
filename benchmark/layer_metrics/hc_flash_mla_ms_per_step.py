"""Summed device durations per step of the latent-attention flash calls
(``flash_fwd_mla``, ``flash_dq_mla``, ``flash_dkv_mla``) in the cell with
residual streams, at 4096 keys and a softmax scale of the caller's
(``mla_kinds.kernels_ms``, as ``flash_mla_ms_per_step``, which lists the
cells it is read in). Nothing where the step has no such call."""
from benchmark import mla_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.kernels_ms(trace, run)
