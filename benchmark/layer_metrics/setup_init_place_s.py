"""Seconds under ``trainer.init.place``, inside ``trainer.init``: the
per-leaf ``device_put`` of the optimizer state onto its shardings (the
span's tag ``leaves`` says how many). ``benchmark/setup_reduce.py``."""
from benchmark import setup_reduce

LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def reduce(trace, run):
    return setup_reduce.span_metric(trace, run, 'trainer.init.place')
