"""Share of its roofline the dkv flash kernel (``flash_dkv``) reaches, as
``flash_roofline_pct`` for this kernel alone. Counted on its own, from
its inputs and outputs, a call needs four score-sized matmuls (the
scores, dP = dO V^T, dV = P^T dO, dK = dS^T Q; half of each under a
causal mask), reads q, k, v, do and writes dk, dv. The dq and dkv
kernels each recompute the scores and dP, so their matmuls add to 7
where ``flash_roofline_pct`` counts flash-attention 2's 5 for the pair:
this share is of what THIS kernel cannot do without."""
from benchmark import scope_reduce

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'

MATMULS = 4    # scores, dP, dV, dK
TENSORS = 6    # reads q k v do, writes dk dv


def reduce(trace, run):
    return scope_reduce.kernel_roofline_pct(trace, run, 'flash_dkv', MATMULS,
                                            TENSORS)
