"""Seconds from the first line of ``run.py`` (its ``T_START``) to the
``trainer.new`` span of the Trainer that trains: Python imports, the
PJRT client and chip bring-up, the harness's own files. Nothing of the
program's is in it, and over ten runs of one tree it read 9.7 to
26.9 s (chip runs of PR 33 and 34, ISSUE 35): the part of ``setup_s``
that holds its noise. ``None`` where the command's module has no
``T_START`` (``benchmark/setup_reduce.py``)."""
from benchmark import setup_reduce

LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def reduce(trace, run):
    return setup_reduce.before_trainer_metric(trace, run)
