"""Summed device durations per step of the grouped flash calls over the
causal half of the score square (``flash_fwd``, ``flash_dq``,
``flash_dkv``: a ``full_attention`` layer's), by the names the program
gives its kernel calls (``flash_kinds.kind_ms``). In a cell with
experts ``flash_ms_per_step`` sums EVERY Pallas call, the grouped
products ``moe_gmm*`` among them: this and
``flash_gqa_band_ms_per_step`` are the flash kernels' milliseconds
alone, and ``flash_ms_per_step`` less the ``moe_gmm*`` calls
(``moe_experts_ms_per_step`` less the activation between them) is
their sum. Nothing where the step has no such call."""
from benchmark import flash_kinds

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return flash_kinds.kind_ms(trace, run, 'global')
