"""Share of their roofline the band flash calls (``flash_fwd_band``,
``flash_dq_band``, ``flash_dkv_band``: a window layer's) reach together:
the least time the chip could take for the calls executed over the time
they took. At ModernBERT's shapes the bound is memory: the same
matmul counts over seq x 129 keys are 1.6% of a global call's FLOPs, the
4 and 8 tensors moved are the same.
The ``say`` line names the bound; costs in ``benchmark/flash_kinds.py``."""
from benchmark import flash_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return flash_kinds.roofline_pct(trace, run, 'window')
