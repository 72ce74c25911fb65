"""Share of the traced window in which no operation ran on a chip (mean
over the chips): 1 - busy / window."""
from benchmark import trace_reduce as tr

LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    busy = tr.chip_mean(trace, lambda chip: tr.busy_ns(trace, chip))
    if busy is None:
        return None
    lo, hi = trace.window
    return 100.0 * (1.0 - busy / (hi - lo))
