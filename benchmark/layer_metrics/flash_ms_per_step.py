"""Summed device durations of the Pallas (Mosaic) custom calls per step
(mean over the chips); 0 where attention runs under XLA."""
from benchmark import trace_reduce as tr

LAYER = 'kernels'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def kernel_events(trace, chip, heads):
    return [e for e in tr.work_ops(trace, chip)
            if tr.op_head(e.name) in heads]


def kernel_ns(trace, heads):
    """Kernel nanoseconds inside the window, mean over the chips."""
    return tr.chip_mean(trace, lambda chip: tr.union_ns(tr.clip(
        kernel_events(trace, chip, heads), trace.window)))


def reduce(trace, run):
    ns = kernel_ns(trace, tr.pallas_heads(run['hlo']))
    return None if ns is None else ns / trace.steps / 1e6
