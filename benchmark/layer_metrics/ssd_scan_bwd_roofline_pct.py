"""Share of its roofline the scan's backward call (``ssd_bwd``) reaches:
what the chunked form's gradients NEED in FLOPs and bytes, from shapes
(``ssm_kinds.call_cost``), over its time in the trace."""
from benchmark import ssm_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return ssm_kinds.roofline_pct(trace, run, 'ssd_bwd')
