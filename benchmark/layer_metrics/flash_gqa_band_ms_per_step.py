"""Summed device durations per step of the grouped causal-band flash
calls (``flash_fwd_band``, ``flash_dq_band``, ``flash_dkv_band``: a
``sliding_attention`` layer's), by the names the program gives its
kernel calls (``flash_kinds.kind_ms``). With
``flash_gqa_causal_ms_per_step`` the flash kernels' milliseconds alone:
``flash_ms_per_step`` less the ``moe_gmm*`` calls is their sum (see
there). Nothing where the step has no such call."""
from benchmark import flash_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return flash_kinds.kind_ms(trace, run, 'window')
