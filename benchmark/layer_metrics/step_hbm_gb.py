"""Device memory the step holds on the fullest chip: its arguments
(weights, optimizer slots, the batch; ``compiled.memory_analysis()``)
plus what the loaded program reserves for its temporaries (the
allocator's ``peak_bytes_reserved``). Room here is room for batch.

Neither usual source has it alone on this installation:
``peak_bytes_in_use`` misses the reservation, and ``memory_analysis()``'s
arguments + outputs + temporaries - aliased comes to 17.4 GB for a step
that runs on a 16.9 GB chip (PERF.md, PR 22)."""
LAYER = 'model step under XLA'
UNIT = 'GB'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'tokens_per_s_per_chip'


def reduce(trace, run):
    reserved = max(s.get('peak_bytes_reserved', 0)
                   for s in run['memory_stats'])
    return (run['memory']['argument'] + reserved) / 1e9
