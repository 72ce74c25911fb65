"""Seconds under ``trainer.init`` of the Trainer that trains: the
parameters' and the optimizer state's making and placement, as far as
the host waits for them (``init`` dispatches and returns; what the
device still has in flight is in the harness's ``block_until_ready``
after it, not here). Set-up as ``benchmark/setup_reduce.py`` bounds
it."""
from benchmark import setup_reduce

LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def reduce(trace, run):
    return setup_reduce.span_metric(trace, run, 'trainer.init')
