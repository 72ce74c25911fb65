"""What the host was doing while the device was idle: the program's loop
spans laid over the idle gaps of the trace (PR 24).

The program's ``Trainer`` records its loop spans (``trainer.input``,
``trainer.step``, ``trainer.loss_readback``, ...) with
``time.perf_counter()`` into a ring of ``autodist_tpu.telemetry`` that
is always on; the benchmark runs in the program's process and reads the
ring after ``fit`` has returned. The two clocks are tied by what a
metric file is handed anyway: ``harness.Feed`` opens each ``fit.step``
annotation and reads ``time.perf_counter()`` on the next line, so
``run['step_times']`` holds, on the host's clock, the instants at which
the ``fit.step`` spans of ``trace.spans`` start on the trace's.

One turn of ``fit``'s loop is ``trainer.input`` (the next batch, with
the placement of the batch two steps ahead), ``trainer.step`` (dispatch
of the compiled step, which returns at once) and
``trainer.loss_readback`` (the host blocked until the device has
finished, then the copy back). The device goes idle inside the
read-back and starts again as the next ``trainer.step`` returns, so a
gap between two steps is the tail of a read-back, an input and a
dispatch. Inside a step the device pauses for microseconds between
operations while the host waits in the read-back: those pauses are not
the read-back's, so only the gap that holds a read-back's END is
counted for it, and they fall under ``unattributed`` with whatever the
loop spends between two spans.

The host plane and the device planes of one trace agree only to about
a millisecond, which is the size of what is split here, so the device
planes are shifted first (:func:`device_shift_ns`).
"""
import bisect
import statistics

from benchmark import trace_reduce as tr

INPUT = 'trainer.input'
STEP = 'trainer.step'
READBACK = 'trainer.loss_readback'
PARTS = ('input', 'dispatch', 'readback', 'unattributed')


def ring_records():
    """The records of the program's loop ring (dicts with ``name``,
    ``t0`` on ``time.perf_counter()``'s clock and ``dur`` in seconds),
    or ``None`` for a program that has no such ring."""
    from autodist_tpu import telemetry
    registry = telemetry.get()
    if not hasattr(registry, 'loop_records'):
        return None
    return registry.loop_records()


def clock_offset_ns(trace, step_times):
    """Nanoseconds to add to ``1e9 x`` a ``perf_counter`` reading to
    land on the trace's clock: the median, over the traced steps, of a
    ``fit.step`` span's start less the reading taken as it opened (the
    last of ``step_times`` is ``fit``'s return and has no span)."""
    steps = [s for s in trace.spans if s.name == tr.STEP_SPAN]
    if not steps or len(step_times) < len(steps):
        return None
    return statistics.median(s.start - 1e9 * t
                             for s, t in zip(steps, step_times))


def spans_on_trace(records, offset_ns, window):
    """``{name: [(start, end)]}`` of the timed ring records that reach
    into ``window``, in trace nanoseconds."""
    lo, hi = window
    out = {}
    for r in records:
        if r['dur'] is None:
            continue
        start = 1e9 * r['t0'] + offset_ns
        end = start + 1e9 * r['dur']
        if end > lo and start < hi:
            out.setdefault(r['name'], []).append((start, end))
    return out


def intersect(a, b):
    """The part of union ``a`` that union ``b`` covers (two walks of
    ``trace_reduce.subtract``, each linear in the two lists)."""
    return tr.subtract(a, tr.subtract(a, b))


def device_shift_ns(trace, spans):
    """Nanoseconds to add to the device planes' times to put them on
    the host plane's clock, or ``None`` where nothing says.

    The profiler aligns the two only to about a millisecond: in
    ``bert-large.s512.c1``'s trace (my chip run, PR 24) a step's first
    operation reads 0.30 ms BEFORE the ``trainer.step`` span that
    dispatched it opens, and 1.21 ms before the runtime's own
    ``DoEnqueueProgram`` event on the host plane. A part of the gap is
    of that size, so the device planes are shifted by the one number
    that puts the device's restart after a gap between two steps at
    the END of the ``trainer.step`` span that dispatched the step
    (median over steps and chips). The convention is off by the
    runtime's enqueue latency after the call returns (0.15 ms in that
    trace), which so counts for the read-back's tail.

    The gaps between two steps are told from the pauses inside one by
    their length: a quarter or more of the median of the chip's
    ``steps`` longest gaps (milliseconds against microseconds). Where
    a placement's few microseconds of device work cut such a gap in
    pieces, the restart is the end of the last piece."""
    lo, hi = trace.window
    near = (hi - lo) / trace.steps / 4
    ends = [end for _, end in spans.get(STEP, [])]
    found = []
    for chip in trace.ops:
        gaps = tr.idle_gaps(trace, chip)           # longest first
        if not gaps:
            continue
        least = statistics.median(
            e - s for s, e in gaps[:trace.steps]) / 4
        restarts = [e for s, e in gaps if e - s >= least]
        for end in ends:
            here = [r for r in restarts if abs(end - r) < near]
            if here:
                found.append(end - max(here))
    return statistics.median(found) if found else None


def chip_split(gaps, spans):
    """Nanoseconds of ``gaps`` (disjoint idle intervals of one chip, on
    the spans' clock) under ``trainer.input``, under ``trainer.step``
    and at the tail of ``trainer.loss_readback``."""
    under_input = tr.union_ns(intersect(gaps, spans.get(INPUT, [])))
    dispatch = tr.union_ns(intersect(gaps, spans.get(STEP, [])))
    readback = 0.0
    gaps = sorted(gaps)
    starts = [g_start for g_start, _ in gaps]
    for start, end in spans.get(READBACK, []):
        i = bisect.bisect_left(starts, end) - 1      # last gap open by then
        if i >= 0 and end <= gaps[i][1]:     # the gap the span ends in
            readback += end - max(start, gaps[i][0])
    return {'input': under_input, 'dispatch': dispatch,
            'readback': readback}


def gap_split(trace, run):
    """``{part: ms of idle a step}`` for :data:`PARTS`, mean over the
    chips; ``unattributed`` is what is left of ``host_gap_ms``, so they
    add up to it. ``None``, with the reason said, where there is
    nothing to read."""
    records = ring_records()
    if records is None:
        run['say']('host gap split: the program has no loop ring '
                   '(autodist_tpu.telemetry.get().loop_records)')
        return None
    offset = clock_offset_ns(trace, run['step_times'])
    if offset is None:
        run['say']('host gap split: %d step times for the trace\'s '
                   'fit.step spans; the clocks cannot be tied'
                   % len(run['step_times']))
        return None
    lo, hi = trace.window
    spans = spans_on_trace(records, offset, (lo, hi))
    run['say']('host gap split: %s of %d loop records inside the traced '
               'window; trace clock = 1e9 x perf_counter %+.0f ns'
               % (', '.join('%d %s' % (len(v), k)
                            for k, v in sorted(spans.items())) or 'none',
                  len(records), offset))
    if not trace.ops or not any(name in spans
                                for name in (INPUT, STEP, READBACK)):
        return None
    shift = device_shift_ns(trace, spans)
    if shift is None:
        run['say']('host gap split: no step\'s first operation near the '
                   'end of a trainer.step span; the device planes '
                   'cannot be tied to the host\'s')
        return None
    run['say']('host gap split: device planes shifted by %+.0f ns, to '
               'start each step where its trainer.step span ends' % shift)
    parts = dict.fromkeys(PARTS[:3], 0.0)
    for chip in trace.ops:
        # the chip's idle intervals on the host plane's clock: taken
        # over the window moved back by the shift, then moved forward
        busy = tr.clip([e for e in trace.ops[chip]
                        if not tr.is_container(e.name)],
                       (lo - shift, hi - shift))
        gaps = [(s + shift, e + shift) for s, e in tr.subtract(
            [(lo - shift, hi - shift)], busy)]
        for part, ns in chip_split(gaps, spans).items():
            parts[part] += ns / len(trace.ops)
    idle = (hi - lo) - tr.chip_mean(trace,
                                    lambda chip: tr.busy_ns(trace, chip))
    parts['unattributed'] = idle - sum(parts.values())
    return {part: ns / trace.steps / 1e6 for part, ns in parts.items()}


def gap_ms(trace, run, part):
    """One of :data:`PARTS`. The split is made once for the ``run`` it is
    asked of and kept on it (``run['host_gap_split']``): the four readers
    share it, and what it says is said once."""
    if 'host_gap_split' not in run:
        run['host_gap_split'] = gap_split(trace, run)
    split = run['host_gap_split']
    return None if split is None else split[part]
