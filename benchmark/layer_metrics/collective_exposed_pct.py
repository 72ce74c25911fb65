"""Share of the traced window in which a collective ran on a chip and
nothing else did: communication not hidden behind compute (mean over
the chips)."""
from benchmark import trace_reduce as tr

LAYER = 'collectives'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    splits = {chip: tr.collective_split(trace, chip) for chip in trace.ops}
    if not any(ran for ran, _ in splits.values()):
        return None
    lo, hi = trace.window
    return 100.0 * tr.chip_mean(trace, lambda chip: splits[chip][1]) \
        / (hi - lo)
