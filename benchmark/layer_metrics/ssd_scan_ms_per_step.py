"""Summed device durations per step of the chunked scan's Pallas calls
(``ssd_fwd``, ``ssd_bwd``), by the names the program gives them
(``ssm_kinds.kernels_ms``); ``flash_ms_per_step`` sums EVERY Pallas
call, these among them. Nothing where the step has no such call."""
from benchmark import ssm_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return ssm_kinds.kernels_ms(trace, run)
