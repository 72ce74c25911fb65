"""Summed device durations per step of the block-diffusion flash calls
(``flash_fwd_bd``, ``flash_dq_bd``, ``flash_dkv_bd``), by the names the
program gives them (``bd_kinds.kernels_ms``). In a cell with experts
``flash_ms_per_step`` sums EVERY Pallas call, the grouped products and
the combine among them: this is the flash kernels' alone. Nothing where
the step has no such call."""
from benchmark import bd_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return bd_kinds.kernels_ms(trace, run)
