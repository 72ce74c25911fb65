"""Median host-clock interval between two of ``fit``'s batch requests:
one whole turn of the user's loop (placement, dispatch, the device step,
the loss read-back)."""
import statistics

LAYER = 'Trainer host loop'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    times = run['step_times']
    if len(times) < 2:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(times, times[1:]))
