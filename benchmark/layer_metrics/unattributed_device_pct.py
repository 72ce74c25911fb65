"""Share of the device's busy time in operations that are in none of the
four phases (forward, recompute, backward, optimizer): operations XLA
made and gave no name (copies, converts, slices), and every operation
whose scope was lost. The guard of the four ``*_ms_per_step`` phases:
they and this share add up to ``device_step_ms``. Where the compiled
step carries none of the program's names (an executable from a cache an
older program filled) the optimizer's operations are in it too, and the
reader says so."""
from benchmark import scope_reduce

LAYER = 'model step under XLA'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    return scope_reduce.unattributed_pct(trace, run)
