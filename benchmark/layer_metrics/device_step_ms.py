"""Device time per step: the union of the intervals in which an
operation ran, over the traced steps (mean over the chips)."""
from benchmark import trace_reduce as tr

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    busy = tr.chip_mean(trace, lambda chip: tr.busy_ns(trace, chip))
    return None if busy is None else busy / trace.steps / 1e6
