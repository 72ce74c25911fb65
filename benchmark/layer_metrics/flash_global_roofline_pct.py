"""Share of their roofline the flash calls over the whole score square
(``flash_fwd``, ``flash_dq``, ``flash_dkv``: a global layer's) reach together:
the least time the chip could take for the calls executed over the time
they took. At ModernBERT's shapes the bound is compute: 2 (forward)
and 5 (backward) score-sized matmuls over seq x seq.
The ``say`` line names the bound; costs in ``benchmark/flash_kinds.py``."""
from benchmark import flash_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return flash_kinds.roofline_pct(trace, run, 'global')
