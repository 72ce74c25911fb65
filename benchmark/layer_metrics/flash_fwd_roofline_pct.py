"""Share of its roofline the forward flash kernel (``flash_fwd``) reaches:
the least time the chip could take for the calls executed over the time
they took, as ``flash_roofline_pct`` for this kernel alone. A call needs
the two score-sized matmuls QK^T and PV (half of each under a causal
mask), reads q, k, v and writes o. Under per-block remat it runs twice a
layer and step; both runs are counted, from the trace."""
from benchmark import scope_reduce

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'

MATMULS = 2    # QK^T, PV
TENSORS = 4    # reads q k v, writes o


def reduce(trace, run):
    return scope_reduce.kernel_roofline_pct(trace, run, 'flash_fwd', MATMULS,
                                            TENSORS)
