"""Summed device durations per step of the latent-attention flash calls
(``flash_fwd_mla``, ``flash_dq_mla``, ``flash_dkv_mla``), by the names
the program gives them (``mla_kinds.kernels_ms``). In a cell with
experts ``flash_ms_per_step`` sums EVERY Pallas call, the grouped
products and the combine among them: this is the flash kernels' alone.
Nothing where the step has no such call."""
from benchmark import mla_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return mla_kinds.kernels_ms(trace, run)
