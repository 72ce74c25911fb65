"""Result bytes of the collectives a chip executed per step: the shapes
from the compiled step's HLO, the executions from the trace, so a
collective inside a ``while`` body (the per-layer gradient all-reduce of
the backward scan) counts once per iteration. (Counted from the HLO
alone, as the repo's first benchmark script did, such a one counts
once.)"""
import re

from benchmark import trace_reduce as tr

LAYER = 'collectives'
UNIT = 'MB'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'

_DTYPE_BYTES = {'pred': 1, 's8': 1, 'u8': 1, 's16': 2, 'u16': 2,
                'bf16': 2, 'f16': 2, 's32': 4, 'u32': 4, 'f32': 4,
                's64': 8, 'u64': 8, 'f64': 8}
# Sync collectives and the '-done' halves of async pairs carry exactly
# the output buffer in their result; a '-start' result also holds the
# operand, which would count the bytes twice.
_COLLECTIVE = re.compile(
    r'(all-reduce|all-gather|reduce-scatter|collective-permute|'
    r'all-to-all)(?:-done)?\(')
_SHAPE = re.compile(r'(\w+)\[([\d,]*)\]')


def collective_bytes(hlo):
    """{head: result bytes} of every collective in the HLO text."""
    out = {}
    for line in hlo.splitlines():
        m = _COLLECTIVE.search(line)
        eq = line.find(' = ')
        if not m or eq < 0 or m.start() < eq:
            continue
        total = 0
        for dtype, dims in _SHAPE.findall(line[eq + 3:m.start()]):
            if dtype not in _DTYPE_BYTES:
                raise ValueError('collective result of unknown type %r'
                                 % dtype)
            size = _DTYPE_BYTES[dtype]
            for d in filter(None, dims.split(',')):
                size *= int(d)
            total += size
        out[tr.op_head(line.replace('ROOT ', '', 1))] = total
    return out


def reduce(trace, run):
    sizes = collective_bytes(run['hlo'])
    if not sizes or not trace.ops:
        return None
    lo, _ = trace.window
    executed = tr.chip_mean(trace, lambda chip: sum(
        sizes.get(tr.op_head(e.name), 0)
        for e in tr.work_ops(trace, chip) if e.start >= lo))
    return executed / trace.steps / 1e6
