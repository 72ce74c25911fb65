"""Device time a step in forward operations run AGAIN inside the backward
pass: the operations under ``jax.checkpoint``'s ``rematted_computation``
(the block's forward under per-block remat, its second ``flash_fwd``
call included). What remat costs; the analytic model FLOPs of
``mfu_pct`` do not count it (``benchmark/scope_reduce.py``; mean over
the chips)."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.phase_ms(trace, run, 'recompute')
