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
"""
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

from autodist_tpu.const import ENV

LOOP_RING = 1024   # records; five a Trainer step


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
    on exit, one record in the always-on loop ring."""

    __slots__ = ('_tel', 'name', 'step', 'tags', '_annotation', '_t0')

    def __init__(self, tel, name, step, tags):
        self._tel = tel
        self.name = name
        self.step = step
        self.tags = tags
        self._annotation = TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        self._tel._record_loop(self.name, self._t0, dur, self.step,
                               self.tags)
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
        # always on, fixed bound: see loop_span
        self._loop = deque(maxlen=LOOP_RING)
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

    def loop_span(self, name, step=None, **tags):
        """A timed context manager for the training loop's own spans,
        recorded whether or not telemetry is enabled: a
        ``jax.profiler.TraceAnnotation`` named ``name`` and a record
        ``{'name', 't0', 'dur', 'step'}`` (``t0`` a ``perf_counter``
        reading, ``dur`` seconds, plus ``tags`` if any) in a ring of
        :data:`LOOP_RING` records (:meth:`loop_records`). With
        telemetry enabled the span also lands in the span buffer like
        any other."""
        return _LoopSpan(self, name, step, tags)

    def loop_event(self, name, step=None, **tags):
        """A point event in the loop ring (``dur`` is ``None``); with
        telemetry enabled also an :meth:`event`."""
        self._record_loop(name, time.perf_counter(), None, step, tags)

    def _record_loop(self, name, t0, dur, step, tags):
        rec = {'name': name, 't0': t0, 'dur': dur, 'step': step}
        if tags:
            rec['tags'] = tags
        with self._lock:
            self._loop.append(rec)
        if self.enabled:
            tags = dict(tags, step=step)
            if dur is None:
                self.event(name, **tags)
            else:
                self._record_span(name, t0, dur, tags)

    def loop_records(self):
        """The loop ring, oldest first (at most :data:`LOOP_RING`)."""
        with self._lock:
            return list(self._loop)

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
