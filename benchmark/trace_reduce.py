"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the few
structures the per-layer metrics read. Uses ``jax.profiler.ProfileData``
and nothing else.

What the trace of this installation looks like (jax 0.9.0, libtpu
0.0.34, TPU v5 lite; looked at by hand in PR 22, see PERF.md §3):

* one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Ops`` holds
  every HLO operation the TensorCore ran, named by its HLO text
  (``%fusion.12 = bf16[...] fusion(...)``); control-flow operations
  (``while``, ``conditional``, ``call``) are events that CONTAIN the
  events of their bodies on the same line, so durations are never
  summed without taking the nesting out (:func:`self_times`,
  :func:`union_ns`). ``XLA Modules`` has one event per executed
  program, ``Steps`` one per program run;
* the host plane ``/host:CPU`` has one line per thread; the
  ``jax.profiler.TraceAnnotation`` spans the benchmark opens
  (``fit.step``, ``data.next``) are events on the main thread's line,
  on the same clock as the device planes.

Times are nanoseconds from the start of the trace.
"""
import gzip
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
HOST_PLANE = '/host:CPU'
OPS_LINE = 'XLA Ops'
STEP_SPAN = 'fit.step'
DATA_SPAN = 'data.next'

_COLLECTIVE = re.compile(
    r'^%?(all-reduce|all-gather|reduce-scatter|collective-permute|'
    r'all-to-all)(-start|-done)?[.\d]*$')
_CONTAINER = re.compile(r'^%?(while|conditional|call)[.\d]*$')


@dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self):
        return self.start + self.dur


@dataclass
class Trace:
    """``ops[chip]``: events of that chip's ``XLA Ops`` line, by start;
    ``spans``: the benchmark's host spans, by start."""
    ops: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)

    @property
    def window(self):
        """(start, end) of the traced steps: first ``fit.step`` span's
        start to the last one's end."""
        steps = [s for s in self.spans if s.name == STEP_SPAN]
        if not steps:
            raise ValueError('the trace has no %r span' % STEP_SPAN)
        return steps[0].start, max(s.end for s in steps)

    @property
    def steps(self):
        return sum(1 for s in self.spans if s.name == STEP_SPAN)


def load(profile):
    """A ``ProfileData`` as a :class:`Trace`."""
    span_names = (STEP_SPAN, DATA_SPAN)
    trace = Trace()
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[int(m.group(1))] = sorted(
                        (Event(e.name, e.start_ns, e.duration_ns)
                         for e in line.events),
                        key=lambda e: (e.start, -e.dur))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                trace.spans.extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events if e.name in span_names)
    trace.spans.sort(key=lambda e: (e.start, -e.dur))
    return trace


def load_file(path):
    """``.xplane.pb``, or a ``.textproto`` / ``.textproto.gz`` of the
    same message (the recorded fixture)."""
    from jax.profiler import ProfileData
    if path.endswith('.gz'):
        with gzip.open(path, 'rt') as f:
            return load(ProfileData.from_text_proto(f.read()))
    if path.endswith('.textproto'):
        with open(path) as f:
            return load(ProfileData.from_text_proto(f.read()))
    return load(ProfileData.from_file(path))


# -- names -----------------------------------------------------------------

def op_head(name):
    """``%fusion.12`` of ``%fusion.12 = bf16[8,128] fusion(...)``: event
    names carry operand text, so nothing is matched past `` = ``."""
    return name.split(' = ', 1)[0].strip()


def is_collective(name):
    return bool(_COLLECTIVE.match(op_head(name)))


def is_container(name):
    return bool(_CONTAINER.match(op_head(name)))


def pallas_heads(hlo):
    """Heads of the Mosaic (Pallas) kernel calls in a compiled step's HLO
    text: the custom calls whose target is ``tpu_custom_call``. Their
    heads say nothing of it (``%closed_call.8``, ``%checkpoint.20``:
    XLA names them after the jaxpr they came from), so the trace's
    events are matched against this set."""
    heads = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line and ' = ' in line:
            heads.add(op_head(line.replace('ROOT ', '', 1)))
    return heads


# -- interval arithmetic ---------------------------------------------------

def clip(events, window):
    """(start, end) pairs of ``events`` cut to ``window``, empty ones
    dropped."""
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def merge(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def union_ns(intervals):
    return sum(t - s for s, t in merge(intervals))


def subtract(a, b):
    """The part of union ``a`` not covered by union ``b``: one walk over
    the two merged lists (both sorted, so an interval of ``b`` that ends
    before one of ``a`` starts is behind every later one of ``a`` too)."""
    out = []
    a, b = merge(a), merge(b)
    j = 0
    for s, t in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < t:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < t:
            out.append((cur, t))
    return out


def self_times(events):
    """[(event, self_ns)]: an event's duration less what the events
    nested inside it cover, so a ``while`` is not counted on top of its
    body. ``events`` sorted by (start, -dur)."""
    out, stack = [], []
    for e in events:
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end, stack[-1][0].end) - e.start
        stack.append([e, e.dur])
    while stack:
        out.append(tuple(stack.pop()))
    return out


# -- what the metrics read -------------------------------------------------

def work_ops(trace, chip):
    """Events that are work, not control flow, inside the window."""
    lo, hi = trace.window
    return [e for e in trace.ops[chip]
            if not is_container(e.name) and e.end > lo and e.start < hi]


def busy_ns(trace, chip):
    """Nanoseconds of the window in which some operation ran on
    ``chip``."""
    return union_ns(clip(work_ops(trace, chip), trace.window))


def chip_mean(trace, per_chip):
    """Mean of ``per_chip(chip)`` over the trace's chips; ``None`` for a
    trace without a device plane, which has nothing to read."""
    if not trace.ops:
        return None
    return sum(per_chip(chip) for chip in trace.ops) / len(trace.ops)


def idle_gaps(trace, chip):
    """(start, end) pairs of the window in which nothing ran on
    ``chip``, longest first."""
    gaps = subtract([trace.window], clip(work_ops(trace, chip),
                                         trace.window))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_span_at(trace, t):
    """The innermost benchmark span that covers time ``t``, or
    ``'outside'``."""
    best = None
    for s in trace.spans:
        if s.start <= t < s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best else 'outside'


def collective_split(trace, chip):
    """(ns a collective ran, ns of that with no other work running) on
    ``chip`` inside the window."""
    ops = work_ops(trace, chip)
    coll = clip([e for e in ops if is_collective(e.name)], trace.window)
    rest = clip([e for e in ops if not is_collective(e.name)],
                trace.window)
    return union_ns(coll), union_ns(subtract(coll, rest))


def breakdown(trace, top=10, gaps=5):
    """The contract's ``breakdown``: the device operations (by head) with
    most self time, and the longest idle gaps by the host span their
    middle fell in; seconds, first chip."""
    if not trace.ops:
        return {'device_ops': [], 'idle_gaps': []}
    chip = min(trace.ops)
    lo, hi = trace.window
    by_head = {}
    for e, self_ns in self_times(trace.ops[chip]):
        if e.end > lo and e.start < hi and not is_container(e.name):
            head = op_head(e.name)
            by_head[head] = by_head.get(head, 0.0) + self_ns
    ops = sorted(by_head.items(), key=lambda kv: -kv[1])[:top]
    idle = [[host_span_at(trace, (s + t) / 2), (t - s) / 1e9]
            for s, t in idle_gaps(trace, chip)[:gaps]]
    return {'device_ops': [[h, ns / 1e9] for h, ns in ops],
            'idle_gaps': idle}


def describe(profile, limit=6):
    """Lines of text that show a trace's structure, for reading one by
    hand: planes, their lines, event counts and the first few names."""
    out = []
    for plane in profile.planes:
        out.append('PLANE %s' % plane.name)
        for line in plane.lines:
            events = list(line.events)
            out.append('  LINE %s: %d events' % (line.name, len(events)))
            for e in events[:limit]:
                out.append('    %.0f +%.0f %s' % (e.start_ns, e.duration_ns,
                                                  e.name[:160]))
    return out


if __name__ == '__main__':
    import sys

    from jax.profiler import ProfileData
    print('\n'.join(describe(ProfileData.from_file(sys.argv[1]))))
