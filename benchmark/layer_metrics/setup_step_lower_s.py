"""Seconds under ``trainer.compile_step.lower``: ``fn.lower(...)`` of
the training step, which is Python tracing of the model under
``value_and_grad`` and lowering to StableHLO with the Mosaic payloads of
the Pallas kernels. Paid whether the persistent cache is warm or cold.
``benchmark/setup_reduce.py``."""
from benchmark import setup_reduce

LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def reduce(trace, run):
    return setup_reduce.span_metric(trace, run,
                                    'trainer.compile_step.lower')
