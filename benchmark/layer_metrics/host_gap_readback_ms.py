"""Device idle time a step at the tail of ``trainer.loss_readback``: from
the device's last operation to ``float(loss)`` returning on the host
(the wake-up and the copy back). Only the idle gap that holds the
span's end counts: the microsecond pauses between operations earlier in
the step are not the read-back's. One of four parts that add up to
``host_gap_ms`` (``benchmark/span_reduce.py``; mean over the chips)."""
from benchmark import span_reduce

LAYER = 'Trainer host loop'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return span_reduce.gap_ms(trace, run, 'readback')
