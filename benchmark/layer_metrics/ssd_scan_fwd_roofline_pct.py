"""Share of its roofline the scan's forward call (``ssd_fwd``) reaches:
the larger of what the chunked form at ``chunk_size`` NEEDS in FLOPs
over peak FLOP/s and in bytes over peak bytes/s, from shapes
(``ssm_kinds.call_cost``), over its time in the trace; the calls the
backward's checkpoint runs again are counted as calls."""
from benchmark import ssm_kinds

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return ssm_kinds.roofline_pct(trace, run, 'ssd_fwd')
