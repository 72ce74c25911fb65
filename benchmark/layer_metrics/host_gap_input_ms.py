"""Device idle time a step while the host was in ``trainer.input``: getting
the next batch, which with ``prefetch`` is the user's ``next`` plus the
placement of the batch two steps ahead. One of four parts that add up to
``host_gap_ms`` (``benchmark/span_reduce.py``: the program's loop spans
laid over the trace's idle gaps; mean over the chips)."""
from benchmark import span_reduce

LAYER = 'Trainer host loop'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return span_reduce.gap_ms(trace, run, 'input')
