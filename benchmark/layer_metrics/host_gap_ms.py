"""Device idle time per step inside the traced window: what the host
loop leaves between one step's last operation and the next one's first
(mean over the chips)."""
from benchmark import trace_reduce as tr

LAYER = 'Trainer host loop'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    busy = tr.chip_mean(trace, lambda chip: tr.busy_ns(trace, chip))
    if busy is None:
        return None
    lo, hi = trace.window
    return ((hi - lo) - busy) / trace.steps / 1e6
