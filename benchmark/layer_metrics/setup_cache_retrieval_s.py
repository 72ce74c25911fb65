"""Seconds of set-up that JAX's persistent compilation cache spent
reading and loading executables it had (the ``jax.cache_retrieval``
records of the program's loop ring, whoever asked). 0 in a run that
hit nothing, as a cold one. ``benchmark/setup_reduce.py``."""
from benchmark import setup_reduce

LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def reduce(trace, run):
    return setup_reduce.covered_metric(trace, run,
                                       setup_reduce.JAX_CACHE)
