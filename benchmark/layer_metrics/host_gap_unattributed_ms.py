"""Device idle time a step under none of ``trainer.input``,
``trainer.step`` and the tail of ``trainer.loss_readback``: the pauses
between operations inside a step and what ``fit``'s loop spends between
two spans. The remainder of ``host_gap_ms`` after the other three parts
(``benchmark/span_reduce.py``; mean over the chips)."""
from benchmark import span_reduce

LAYER = 'Trainer host loop'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return span_reduce.gap_ms(trace, run, 'unattributed')
