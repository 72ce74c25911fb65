"""Device idle time a step under ``trainer.counters_readback``: ``fit``
reading back what the model counted in the step (a ``float()`` for each
counter, after the loss's), while the device waits for the next
dispatch. Only a model that counts has the span (the expert layer's
three counters in Mellum2's cell). A part OF
``host_gap_unattributed_ms``, which is what is under none of the three
spans ``benchmark/span_reduce.py`` knows; clocks and device planes are
tied by its functions as they are (mean over the chips)."""
from benchmark import span_reduce
from benchmark import trace_reduce as tr

LAYER = 'Trainer host loop'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'tokens_per_s_per_chip'

SPAN = 'trainer.counters_readback'


def reduce(trace, run):
    say = run['say']
    records = span_reduce.ring_records()
    if not trace.ops or records is None:
        say('%s: no device plane or no loop ring: nothing to read' % SPAN)
        return None
    offset = span_reduce.clock_offset_ns(trace, run['step_times'])
    if offset is None:
        say('%s: the clocks cannot be tied' % SPAN)
        return None
    lo, hi = trace.window
    spans = span_reduce.spans_on_trace(records, offset, (lo, hi))
    shift = span_reduce.device_shift_ns(trace, spans)
    if SPAN not in spans or shift is None:
        say('%s: %d such spans inside the traced window, device planes %s'
            % (SPAN, len(spans.get(SPAN, [])),
               'not tied to the host\'s' if shift is None else 'tied'))
        return None
    under = [(max(s, lo), min(e, hi)) for s, e in spans[SPAN]]
    idle = 0.0
    for chip in trace.ops:
        # the chip's operations on the host plane's clock, as
        # span_reduce.gap_split takes them
        busy = [(s + shift, e + shift) for s, e in tr.clip(
            [e for e in trace.ops[chip] if not tr.is_container(e.name)],
            (lo - shift, hi - shift))]
        idle += tr.union_ns(tr.subtract(under, busy)) / len(trace.ops)
    return idle / trace.steps / 1e6
