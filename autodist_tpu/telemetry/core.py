"""Low-overhead span / counter / gauge registry — the process-local
half of the telemetry plane.

Every subsystem shipped since PR 1 grew its own ad-hoc stats dict
(``ps_stats``, ``health_stats``, the bucket/overlap/sparse/health
reports in :mod:`autodist_tpu.utils.profiling`) — all worker-local,
none exportable, none captured when a run dies. This module is the
shared substrate they now feed: timed **spans** (``with tel.span(
'push_deltas', step=3):``), point **events**, monotonic **counters**,
last-value **gauges** and bounded numeric **series** (e.g. the uniform
per-step wall series ``Session.run`` records), all in one registry a
worker can snapshot (:meth:`Telemetry.metrics_snapshot`), batch-push
over the PS plane (:mod:`autodist_tpu.telemetry.aggregate`) and embed
in BENCH records.

Cost contract (the tentpole's overhead budget):

- **disabled** (``AUTODIST_TELEMETRY`` unset, the default): zero-cost
  no-ops — ``span()`` returns one shared null context manager (no
  allocation, no clock read) and every other recording call returns
  after a single attribute check;
- **enabled**: one ``perf_counter`` pair + one bounded-deque append
  per span (~3 us measured); batch pushes ride the session's
  dedicated background lane, never the step's critical path. What
  the always-on loop spans cost a training step on the chip is in
  ``PERF.md`` (PR 24's entry).

Buffers are bounded (``AUTODIST_TELEMETRY_MAX_SPANS``): telemetry must
never grow without bound on a long run — old spans fall off the front
once drained batches stop being pushed.

Thread safety: recording calls take a small lock (the session's
pipeline/heartbeat threads and ``TransferPool`` workers all record);
the lock is only reached when telemetry is enabled.

The one exception to the gate is the **loop ring**
(:meth:`Telemetry.loop_span`, :meth:`Telemetry.loop_event`): the
``Trainer``'s handful of loop spans a step are recorded whether or not
``AUTODIST_TELEMETRY`` is set, into a ring of :data:`LOOP_RING`
records, because whoever profiles a run (the benchmark's traced run, an
operator's ``Trainer.profile``) cannot switch anything on in a process
that is already training. Each also opens a
``jax.profiler.TraceAnnotation`` of the same name, which costs next to
nothing while no profiler runs and puts the span on the host plane of
the trace, beside the runtime's own events, when one does.

A ring record says who caused it: ``id`` counts the records of the
process and ``parent`` is the ``id`` of the loop span that was open on
the recording thread, so a span's self time is its duration less what
its children cover. A span opened with ``setup=True`` (the ``Trainer``'s
``new`` / ``init`` / ``compile_step``) and everything recorded under it
go to a list of :data:`LOOP_SETUP` records that nothing turns over: a
long ``fit`` fills the ring in 200 steps, and how the job started is
asked after that. JAX's own compile durations (:data:`JAX_DURATIONS`)
land in the same place, under the span that caused them.
"""
import itertools
import threading
import time
from collections import deque

import jax.monitoring
from jax.profiler import TraceAnnotation

from autodist_tpu.const import ENV

LOOP_RING = 1024   # records; six a Trainer step
LOOP_SETUP = 1024  # records of set-up kept for the life of the process

# jax 0.9.0's duration events (jax/_src/dispatch.py, compiler.py) and
# the ring record each becomes. ``jax.backend_compile`` is the whole
# compile request, so on a persistent-cache hit it holds a
# ``jax.cache_retrieval``.
JAX_DURATIONS = {
    '/jax/core/compile/jaxpr_trace_duration': 'jax.trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'jax.lower',
    '/jax/core/compile/backend_compile_duration': 'jax.backend_compile',
    '/jax/compilation_cache/cache_retrieval_time_sec':
        'jax.cache_retrieval',
}

# JAX reports the trace of every jitted function it meets INSIDE another
# trace as an event of its own (each ``jnp.add`` of a step: hundreds,
# 10 us apiece, all within the outer event): those are not recorded
MIN_TRACE_S = 1e-3

_IDS = itertools.count()      # per process: unique across reset()
_OPEN = threading.local()     # .spans: this thread's open _LoopSpans


def _open_spans():
    try:
        return _OPEN.spans
    except AttributeError:
        spans = _OPEN.spans = []
        return spans


class _NullSpan:
    """The disabled-path context manager: one shared instance, no
    state, so ``tel.span(...)`` costs an attribute check and nothing
    else when telemetry is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records its duration into the registry on exit."""

    __slots__ = ('_tel', 'name', 'tags', '_t0')

    def __init__(self, tel, name, tags):
        self._tel = tel
        self.name = name
        self.tags = tags

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc):
        t1 = time.perf_counter()
        if exc_type is not None:
            self.tags['error'] = exc_type.__name__
        self._tel._record_span(self.name, self._t0, t1 - self._t0,
                               self.tags)
        return False


class _LoopSpan:
    """One live loop span: a ``TraceAnnotation`` for the profiler and,
    on exit, one record in the always-on loop ring. While it is open it
    is the top of its thread's stack, so what is recorded meanwhile
    names it as ``parent``."""

    __slots__ = ('_tel', 'name', 'step', 'tags', 'setup', 'id', 'parent',
                 '_annotation', '_t0')

    def __init__(self, tel, name, step, setup, tags):
        self._tel = tel
        self.name = name
        self.step = step
        self.setup = setup
        self.tags = tags
        self._annotation = TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        spans = _open_spans()
        self.id = next(_IDS)
        if spans:
            self.parent = spans[-1].id
            self.setup = self.setup or spans[-1].setup
        else:
            self.parent = None
        spans.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        _open_spans().pop()
        self._annotation.__exit__(*exc)
        self._tel._record_loop(self.name, self._t0, dur, self.step,
                               self.tags, self.id, self.parent, self.setup)
        return False


class Telemetry:
    """The per-process telemetry registry.

    Use the module-level singleton (:func:`get`) — one registry per
    process is the point: the session's step loop, the coord client's
    RPCs and the plan's bucket emission all land in the same buffers,
    so one snapshot/batch covers the whole worker.
    """

    def __init__(self, enabled=None, max_spans=None):
        self.enabled = (ENV.AUTODIST_TELEMETRY.val
                        if enabled is None else bool(enabled))
        cap = (ENV.AUTODIST_TELEMETRY_MAX_SPANS.val
               if max_spans is None else int(max_spans))
        self._lock = threading.Lock()
        # wall anchor: span t0s are perf_counter offsets mapped onto
        # the wall clock ONCE here, so cross-worker aggregation can
        # place spans on a shared (wall) axis without per-span
        # time.time() calls on the hot path
        self._anchor_wall = time.time()
        self._anchor_perf = time.perf_counter()
        self._spans = deque(maxlen=cap)
        self._events = deque(maxlen=cap)
        # always on, fixed bounds: see loop_span
        self._loop = deque(maxlen=LOOP_RING)
        self._loop_setup = []
        # cumulative per-span-name aggregates: survive both the ring
        # bound and drain_spans (the periodic batch push), like the
        # series' count/total — the snapshot must describe the whole
        # run, not just the undrained tail
        self._span_agg = {}
        self.counters = {}
        self.gauges = {}
        self._series = {}
        self._series_cap = cap

    # -- recording ---------------------------------------------------------
    def span(self, name, **tags):
        """A timed context manager. Tags ride the record verbatim
        (keep them small scalars: step=, worker=, cmd=, bytes=)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, tags)

    def record_span(self, name, t0, dur, **tags):
        """Record an already-measured span (``t0`` a ``perf_counter``
        value, ``dur`` seconds) — for callers that only know after the
        fact whether the interval deserves a span (e.g. ``Session.run``
        tagging only executed train steps)."""
        if not self.enabled:
            return
        self._record_span(name, t0, dur, tags)

    def _record_span(self, name, t0, dur, tags):
        rec = {'name': name,
               't0': self._anchor_wall + (t0 - self._anchor_perf),
               'dur': dur}
        if tags:
            rec['tags'] = tags
        with self._lock:
            self._spans.append(rec)
            agg = self._span_agg.setdefault(
                name, {'count': 0, 'total_s': 0.0})
            agg['count'] += 1
            agg['total_s'] += dur

    def loop_span(self, name, step=None, setup=False, **tags):
        """A timed context manager for the training loop's own spans,
        recorded whether or not telemetry is enabled: a
        ``jax.profiler.TraceAnnotation`` named ``name`` and a record
        ``{'name', 't0', 'dur', 'step', 'id', 'parent'}`` (``t0`` a
        ``perf_counter`` reading, ``dur`` seconds, ``parent`` the
        ``id`` of the loop span open on this thread as this one opened
        or ``None``, plus ``tags`` if any) in a ring of
        :data:`LOOP_RING` records (:meth:`loop_records`). With
        ``setup`` the record, and every record made on this thread
        while the span is open, is kept outside the ring, in a list of
        at most :data:`LOOP_SETUP`. With telemetry enabled the span
        also lands in the span buffer like any other."""
        return _LoopSpan(self, name, step, setup, tags)

    def loop_event(self, name, step=None, **tags):
        """A point event in the loop ring (``dur`` is ``None``), a
        child of the loop span open on this thread; with telemetry
        enabled also an :meth:`event`."""
        self._record_under_open(name, time.perf_counter(), None, step,
                                tags)

    def _record_under_open(self, name, t0, dur, step, tags):
        spans = _open_spans()
        parent, setup = ((spans[-1].id, spans[-1].setup) if spans
                         else (None, False))
        self._record_loop(name, t0, dur, step, tags, next(_IDS), parent,
                          setup)

    def _record_loop(self, name, t0, dur, step, tags, id_, parent, setup):
        rec = {'name': name, 't0': t0, 'dur': dur, 'step': step,
               'id': id_, 'parent': parent}
        if tags:
            rec['tags'] = tags
        with self._lock:
            if setup and len(self._loop_setup) < LOOP_SETUP:
                self._loop_setup.append(rec)
            else:
                self._loop.append(rec)
        if self.enabled:
            tags = dict(tags, step=step)
            if dur is None:
                self.event(name, **tags)
            else:
                self._record_span(name, t0, dur, tags)

    def loop_records(self):
        """The kept set-up records (at most :data:`LOOP_SETUP`) and the
        loop ring (at most :data:`LOOP_RING`) as one list, in the order
        they were recorded: a span as it closed, so after its
        children."""
        with self._lock:
            records = self._loop_setup + list(self._loop)
        records.sort(key=lambda r: r['t0'] + (r['dur'] or 0.0))
        return records

    def event(self, name, **tags):
        """A point (instant) event."""
        if not self.enabled:
            return
        rec = {'name': name, 't0': self._anchor_wall +
               (time.perf_counter() - self._anchor_perf)}
        if tags:
            rec['tags'] = tags
        with self._lock:
            self._events.append(rec)

    def count(self, name, delta=1):
        """Bump a monotonic counter."""
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + delta

    def gauge(self, name, value):
        """Set a last-value gauge."""
        if not self.enabled:
            return
        with self._lock:
            self.gauges[name] = value

    def observe(self, name, value):
        """Append to a bounded numeric series (count/total survive the
        ring bound, so means stay exact over the whole run)."""
        if not self.enabled:
            return
        with self._lock:
            s = self._series.get(name)
            if s is None:
                s = self._series[name] = {
                    'values': deque(maxlen=self._series_cap),
                    'count': 0, 'total': 0.0}
            s['values'].append(value)
            s['count'] += 1
            s['total'] += value

    # -- reading -----------------------------------------------------------
    def series_values(self, name):
        """The retained values of one series (most recent
        ``AUTODIST_TELEMETRY_MAX_SPANS``), oldest first."""
        with self._lock:
            s = self._series.get(name)
            return list(s['values']) if s else []

    def drain_spans(self):
        """Pop every buffered span + event record (the batch the
        session pushes to the PS telemetry namespace)."""
        with self._lock:
            out = list(self._spans) + list(self._events)
            self._spans.clear()
            self._events.clear()
        return out

    def metrics_snapshot(self):
        """One JSON-serializable snapshot of the whole registry:
        counters, gauges, per-series stats and per-span-name
        aggregates. Embedded in the chief's cohort timeline."""
        with self._lock:
            by_name = {}
            for name, agg in self._span_agg.items():
                by_name[name] = {
                    'count': agg['count'],
                    'total_s': round(agg['total_s'], 6),
                    'mean_s': round(agg['total_s'] / agg['count'], 6)}
            series = {}
            for name, s in self._series.items():
                vals = list(s['values'])
                series[name] = {
                    'count': s['count'],
                    'total': round(s['total'], 6),
                    'mean': round(s['total'] / s['count'], 6)
                    if s['count'] else 0.0,
                    'last': vals[-1] if vals else None}
            return {'enabled': self.enabled,
                    'counters': dict(self.counters),
                    'gauges': dict(self.gauges),
                    'series': series,
                    'spans': by_name,
                    'buffered_spans': len(self._spans),
                    'buffered_events': len(self._events)}


_SINGLETON = None
_SINGLETON_LOCK = threading.Lock()


def get():
    """The process-wide registry (created on first use; the enabled
    flag is read from ``AUTODIST_TELEMETRY`` at creation — tests that
    flip the env call :func:`reset`)."""
    global _SINGLETON
    tel = _SINGLETON
    if tel is None:
        with _SINGLETON_LOCK:
            tel = _SINGLETON
            if tel is None:
                tel = _SINGLETON = Telemetry()
    return tel


def reset():
    """Drop the singleton so the next :func:`get` re-reads the env
    (test/bench A/B hook; production processes never need it)."""
    global _SINGLETON
    with _SINGLETON_LOCK:
        _SINGLETON = None


def _on_jax_duration(event, duration, **kwargs):
    """One timed ring record for each of :data:`JAX_DURATIONS`, ending
    now, under the loop span open on the compiling thread. JAX reports
    these only when something is traced, lowered or compiled: never in
    a steady step."""
    name = JAX_DURATIONS.get(event)
    if name is None or (name == 'jax.trace' and duration < MIN_TRACE_S):
        return
    tags = {}
    if 'fun_name' in kwargs:
        tags['fun_name'] = kwargs['fun_name']
    get()._record_under_open(name, time.perf_counter() - duration,
                             duration, None, tags)


# once per process and handed to whichever registry is current: a
# listener cannot be taken off, so reset() must not add another
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
