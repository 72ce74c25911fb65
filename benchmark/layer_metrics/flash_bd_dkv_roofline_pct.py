"""Share of its roofline the block-diffusion call ``flash_dkv_bd`` reaches
(the scores again, dV, dP and dK, 4 score-sized products
over the mask's ``L^2 + L B`` live pairs a sequence and query head at
128 lanes, k and v once a group): the larger of its FLOPs over peak
FLOP/s and its bytes over peak bytes/s, from shapes
(``bd_kinds.call_cost``), over its time in the trace."""
from benchmark import bd_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return bd_kinds.roofline_pct(trace, run, 'flash_dkv_bd')
