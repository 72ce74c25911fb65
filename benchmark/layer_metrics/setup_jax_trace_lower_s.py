"""Seconds of set-up that JAX spent tracing Python to jaxprs and
lowering jaxprs to StableHLO, whoever asked (the Trainer that trains,
the harness's probe Trainer, the reference, the small jits of ``init``):
the time covered by the ``jax.trace`` and ``jax.lower`` records of the
program's loop ring. No cache shortens it.
``benchmark/setup_reduce.py``."""
from benchmark import setup_reduce

LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def reduce(trace, run):
    return setup_reduce.covered_metric(trace, run,
                                       setup_reduce.JAX_TRACE_LOWER)
