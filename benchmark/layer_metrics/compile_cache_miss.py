"""Compile requests during set-up that the persistent cache did not
serve; 0 in every run of a cell after its first in a checkout."""
LAYER = 'entry point and compile'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'setup_s'


def reduce(trace, run):
    return run['compile']['requests'] - run['compile']['cache_hits']
