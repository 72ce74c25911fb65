"""Time per step in which a collective operation ran on a chip (mean
over the chips)."""
from benchmark import trace_reduce as tr

LAYER = 'collectives'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    ran = tr.chip_mean(trace,
                       lambda chip: tr.collective_split(trace, chip)[0])
    return ran / trace.steps / 1e6 if ran else None
