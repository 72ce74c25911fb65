"""Seconds under ``trainer.compile_step.compile``: ``.compile()`` of
the lowered step, which is XLA's compile where the persistent cache is
cold and the cache's key, look-up, read and load where it is warm.
``benchmark/setup_reduce.py``."""
from benchmark import setup_reduce

LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def reduce(trace, run):
    return setup_reduce.span_metric(trace, run,
                                    'trainer.compile_step.compile')
