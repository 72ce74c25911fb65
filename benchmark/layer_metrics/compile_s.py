"""Seconds spent in backend compile requests during set-up
(``jax.monitoring`` durations; a request served by the persistent
cache is a short one)."""
LAYER = 'entry point and compile'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'setup_s'


def reduce(trace, run):
    return run['compile']['seconds']
