"""Where set-up goes, from inside: the program's own spans through
``Trainer.__init__``, ``init`` and ``compile_step``, and JAX's trace,
lower, compile and cache-load durations under them (PR 35).

``setup_s`` is the host's clock from the first line of ``run.py`` to
the measured ``fit``. The program records, in the loop ring of
``autodist_tpu.telemetry`` (``span_reduce.ring_records``), a span round
each part of that which is its own, every record with an ``id``, the
``id`` of the span that was open as it was made (``parent``) and, on
the ``Trainer``'s own, which Trainer made it (the tag ``trainer``, the
order of construction: the harness builds the one that trains first
and its probe second). JAX's durations are ring records too
(``jax.trace``, ``jax.lower``, ``jax.backend_compile``,
``jax.cache_retrieval``), under whichever span was open.

**Set-up** here is what the ring holds from before the ``t0`` of the
last ``trainer.fit`` record of the Trainer with the lowest tag: that is
the measured call, which has returned when the readers run, and the
warm-up's ``fit`` is set-up, as it is in ``setup_s``. All times are
``time.perf_counter()``'s, as ``run.py``'s ``T_START`` is.

Each reader returns ``None`` and says why where there is nothing to
read: a trace with no device plane (the tests' rehearsal on the CPU,
whose timings are no chip's), a program whose records carry no
``trainer`` tag (any before PR 35), a span that never ran.
"""
import json
import sys

from benchmark import span_reduce
from benchmark import trace_reduce as tr

FIT = 'trainer.fit'
NEW = 'trainer.new'
JAX_TRACE_LOWER = ('jax.trace', 'jax.lower')
JAX_CACHE = ('jax.cache_retrieval',)


def tag_of(record):
    return (record.get('tags') or {}).get('trainer')


def setup_of(records):
    """``(set-up's records, the measured Trainer's tag)``, or ``(None,
    reason)``."""
    tags = [tag_of(r) for r in records if tag_of(r) is not None]
    if not tags:
        return None, ('no loop record carries the tag `trainer`: the '
                      'program does not say which Trainer made a span')
    trainer = min(tags)
    fits = [r['t0'] for r in records
            if r['name'] == FIT and tag_of(r) == trainer]
    if not fits:
        return None, ('no %s record of trainer %d: no measured call to '
                      'end set-up at' % (FIT, trainer))
    end = max(fits)
    return [r for r in records if r['t0'] < end], trainer


def interval(record):
    return record['t0'], record['t0'] + (record['dur'] or 0.0)


def span_s(setup, trainer, name):
    """Seconds under the measured Trainer's spans called ``name``, or
    ``None`` where it made none."""
    durs = [r['dur'] for r in setup
            if r['name'] == name and tag_of(r) == trainer
            and r['dur'] is not None]
    return sum(durs) if durs else None


def covered_s(setup, names):
    """Seconds that the records called one of ``names`` cover, whoever
    caused them: the union, because JAX times a function traced inside
    another trace on its own AND inside the outer one. 0 where the
    program records JAX's durations and made none of these (no cache
    hit in a cold run); ``None`` where it records none at all."""
    found = [interval(r) for r in setup
             if r['name'] in names and r['dur'] is not None]
    if not found and not any(r['name'].startswith('jax.') for r in setup):
        return None     # a program that does not record JAX's durations
    # (union_ns is the length of a union in the intervals' own unit)
    return tr.union_ns(found)


def before_trainer_s(setup, trainer, t_start):
    """Seconds from ``t_start`` to the measured Trainer's
    ``trainer.new``: what the process did before the program was asked
    for anything."""
    news = [r['t0'] for r in setup
            if r['name'] == NEW and tag_of(r) == trainer]
    return min(news) - t_start if news else None


def self_s(record, setup):
    """``record``'s duration less what its children cover."""
    lo, hi = interval(record)
    children = [(max(lo, s), min(hi, e)) for s, e in (
        interval(r) for r in setup if r.get('parent') == record['id'])]
    return record['dur'] - tr.union_ns([c for c in children if c[1] > c[0]])


def summary(setup, trainer):
    """``{name: [count, seconds, self seconds]}`` of the measured
    Trainer's timed spans in set-up, ``{name: [count, seconds
    covered]}`` of JAX's records there, and ``{span: {name: seconds
    covered}}`` of JAX's records directly under each of those spans, for
    the table of PERF.md §5."""
    spans, jax_records, under = {}, {}, {}
    mine = {r['id']: r['name'] for r in setup
            if tag_of(r) == trainer and 'id' in r}
    for r in setup:
        if r['dur'] is None:
            continue
        if r['name'].startswith('jax.'):
            jax_records.setdefault(r['name'], []).append(r)
            if r.get('parent') in mine:
                under.setdefault(mine[r['parent']], {}).setdefault(
                    r['name'], []).append(interval(r))
        elif r.get('id') in mine:
            row = spans.setdefault(r['name'], [0, 0.0, 0.0])
            row[0] += 1
            row[1] += r['dur']
            row[2] += self_s(r, setup)
    return {'spans': {k: [v[0], round(v[1], 4), round(v[2], 4)]
                      for k, v in spans.items()},
            'jax': {k: [len(v), round(tr.union_ns(
                [interval(r) for r in v]), 4)]
                for k, v in jax_records.items()},
            'under': {span: {k: round(tr.union_ns(v), 4)
                             for k, v in by_name.items()}
                      for span, by_name in under.items()}}


def main_t_start():
    """``T_START`` of the module run as the command (``run.py`` reads
    the clock on its first line), or ``None``."""
    return getattr(sys.modules.get('__main__'), 'T_START', None)


def read(trace, run, reader, says_summary=False):
    """``reader(setup, trainer)`` over this process's ring, or ``None``
    with the reason said. The seven readers share the summary, so only
    the one that asks for it says it."""
    say = run['say']
    if not trace.ops:
        say('set-up from inside: the trace has no device plane, so this '
            'is no chip\'s run: nothing is reported')
        return None
    records = span_reduce.ring_records()
    if records is None:
        say('set-up from inside: the program has no loop ring '
            '(autodist_tpu.telemetry.get().loop_records)')
        return None
    setup, trainer = setup_of(records)
    if setup is None:
        say('set-up from inside: ' + trainer)
        return None
    if says_summary:
        say('set-up from inside: trainer %d, %d of %d loop records before '
            'the measured fit: %s' % (trainer, len(setup), len(records),
                                      json.dumps(summary(setup, trainer))))
    value = reader(setup, trainer)
    if value is None:
        say('set-up from inside: no record for this reader in set-up')
    return value


def span_metric(trace, run, name):
    return read(trace, run, lambda setup, trainer: span_s(
        setup, trainer, name))


def covered_metric(trace, run, names):
    return read(trace, run, lambda setup, trainer: covered_s(setup, names))


def before_trainer_metric(trace, run):
    t_start = main_t_start()
    if t_start is None:
        run['say']('set-up from inside: the command\'s module has no '
                   'T_START: no clock to count from')
        return None
    return read(trace, run, lambda setup, trainer: before_trainer_s(
        setup, trainer, t_start), says_summary=True)
