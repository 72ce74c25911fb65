"""Device idle time a step while the host was in ``trainer.step``: the
step's key, the look-up of the compiled step, ``shard_batch`` and the
call, up to the device's first operation. One of four parts that add up
to ``host_gap_ms`` (``benchmark/span_reduce.py``; mean over the chips)."""
from benchmark import span_reduce

LAYER = 'Trainer host loop'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return span_reduce.gap_ms(trace, run, 'dispatch')
