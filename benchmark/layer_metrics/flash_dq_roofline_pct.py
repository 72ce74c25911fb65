"""Share of its roofline the dq flash kernel (``flash_dq``) reaches, as
``flash_roofline_pct`` for this kernel alone. Counted on its own, from
its inputs and outputs, a call needs three score-sized matmuls (the
scores, dP = dO V^T, dQ = dS K; half of each under a causal mask), reads
q, k, v, do and writes dq (lse and delta are a 64th of a tensor). The
dq and dkv kernels each recompute the scores and dP, so their matmuls
add to 7 where ``flash_roofline_pct`` counts flash-attention 2's 5 for
the pair: this share is of what THIS kernel cannot do without."""
from benchmark import scope_reduce

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'

MATMULS = 3    # scores, dP, dQ
TENSORS = 5    # reads q k v do, writes dq


def reduce(trace, run):
    return scope_reduce.kernel_roofline_pct(trace, run, 'flash_dq', MATMULS,
                                            TENSORS)
