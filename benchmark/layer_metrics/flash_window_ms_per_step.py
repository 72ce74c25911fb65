"""Summed device durations per step of the band flash calls
(``flash_fwd_band``, ``flash_dq_band``, ``flash_dkv_band``: a window layer's), told from
the other kind by the names the program gives its kernel calls
(``benchmark/flash_kinds.py``); the two kinds add up to
``flash_ms_per_step``. Nothing where the step has no such call."""
from benchmark import flash_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return flash_kinds.kind_ms(trace, run, 'window')
