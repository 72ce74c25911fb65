"""What the training step says about itself (PR 24): the named scopes and
kernel names in the compiled step, and the ``Trainer``'s loop spans in
the always-on ring of ``telemetry``. All on the CPU mesh, no subprocess."""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.api import Trainer
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.axes import ParallelSpec
from autodist_tpu.telemetry import core


def batch_of(seq, rows=4, seed=0):
    rng = np.random.RandomState(seed)
    return {'tokens': rng.randint(0, 256, (rows, seq), dtype=np.int32),
            'targets': rng.randint(0, 256, (rows, seq), dtype=np.int32)}


def tiny_trainer(**cfg):
    model = TransformerLM(TransformerConfig.tiny(max_len=32, **cfg))
    return Trainer(model, optax.adamw(1e-3), spec=ParallelSpec(dp=1))


@pytest.fixture
def ring(monkeypatch):
    """A fresh registry with ``AUTODIST_TELEMETRY`` unset."""
    monkeypatch.delenv('AUTODIST_TELEMETRY', raising=False)
    telemetry.reset()
    yield telemetry.get()
    telemetry.reset()


def names(records):
    """(name, step) of the records the program's own sites make: JAX's
    (``jax.*``) depend on what the process has traced before."""
    return [(r['name'], r['step']) for r in records
            if not r['name'].startswith('jax.')]


def by_name(records, name):
    return [r for r in records if r['name'] == name]


# -- the device: scopes and kernel names -----------------------------------

def test_compiled_step_carries_every_scope_name():
    tr = tiny_trainer(remat=True)
    state = tr.init(jax.random.PRNGKey(0))
    text = tr.compile_step(state, batch_of(32)).as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for wanted in ('jit(step_fn)/optimizer/',
                   'jit(step_fn)/jvp(embed)/',
                   'jit(step_fn)/transpose(jvp(embed))/',
                   'jit(step_fn)/jvp(head_loss)/',
                   'jit(step_fn)/transpose(jvp(head_loss))/',
                   'jvp()/while/body/closed_call/block/attention/',
                   'jvp()/while/body/closed_call/block/mlp/',
                   'transpose(jvp())/while/body/closed_call/checkpoint/'
                   'block/mlp/',
                   'transpose(jvp())/while/body/closed_call/checkpoint/'
                   'rematted_computation/block/attention/'):
        assert any(wanted in name for name in op_names), wanted


def test_kernel_names_reach_the_tpu_lowering(monkeypatch):
    """``pallas_call(name=...)`` is what tells dq from dkv in the
    compiled step; interpret mode on the CPU drops it, so look at the
    step lowered for the TPU (as ``test_tpu_bringup.py`` does)."""
    from autodist_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(fa, '_interpret_default', lambda: False)
    cfg = TransformerConfig.tiny(dtype=jnp.bfloat16, n_layers=1, max_len=512,
                                 remat=True)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1), spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    batch = batch_of(512)
    t_before = time.perf_counter()
    step = tr._ensure_step(tr._step_key(batch), state, batch)
    module = jax.export.export(step, platforms=['tpu'])(
        state, tr.shard_batch(batch)).mlir_module()
    # each trace of a call leaves its static plan in the loop ring; since
    # PR 29 with the layout the kernels work on, [b, s, heads * head_dim],
    # and how the heads sit in its lanes (four heads of 16 are under 128
    # lanes in all: one block of 64)
    plans = [r['tags'] for r in telemetry.get().loop_records()
             if r['t0'] >= t_before and r['name'] == 'flash.plan']
    assert plans and all(
        (t['layout'], t['lane_block'], t['heads_per_lane_block'],
         t['head_dim'], t['seq']) == ('bsd', 64, 4, 16, 512) for t in plans)
    assert all(t[k + 'heads_per_step'] == 4
               for t in plans for k in ('', 'dq_', 'dkv_'))
    calls = [line for line in module.splitlines() if 'tpu_custom_call' in line]
    # forward, dq, dkv: 4 until PR 27, when the block's checkpoint began
    # to keep the forward kernel's o and lse and the backward stopped
    # calling it again
    assert len(calls) == 3
    for kernel in ('flash_fwd', 'flash_dq', 'flash_dkv'):
        assert sum('kernel_name = "%s"' % kernel in line
                   for line in calls) == 1, kernel


# -- the host: the loop ring -----------------------------------------------

def test_fit_leaves_its_spans_in_the_ring_with_telemetry_off(ring):
    assert not ring.enabled
    tr = tiny_trainer()
    state = tr.init(jax.random.PRNGKey(0))
    state, history = tr.fit(state, iter([batch_of(32, seed=i)
                                         for i in range(5)]),
                            steps=3, prefetch=2)
    assert len(history['loss']) == 3
    records = ring.loop_records()
    assert names(records) == [
        ('trainer.new', None),
        ('trainer.init.params', None), ('trainer.init.opt_state', None),
        ('trainer.init.place', None), ('trainer.init', None),
        # the prefetcher fills two batches before the first is handed out
        ('trainer.source', 1), ('trainer.place', 1),
        ('trainer.source', 2), ('trainer.place', 2),
        ('trainer.source', 3), ('trainer.place', 3), ('trainer.input', 1),
        ('trainer.new_step_signature', 1), ('trainer.step', 1),
        ('trainer.loss_readback', 1),
        ('trainer.source', 4), ('trainer.place', 4), ('trainer.input', 2),
        ('trainer.step', 2), ('trainer.loss_readback', 2),
        ('trainer.source', 5), ('trainer.place', 5), ('trainer.input', 3),
        ('trainer.step', 3), ('trainer.loss_readback', 3),
        ('trainer.fit', 1)]
    fit = records[-1]
    assert fit['tags'] == {'trainer': tr._tag, 'steps': 3, 'prefetch': 2}
    for r in records:
        if r['name'] == 'trainer.new_step_signature':
            assert r['dur'] is None and '(4, 32)' in r['tags']['shapes']
        else:       # inside fit's span, on perf_counter's clock
            assert r['dur'] >= 0
            if r['id'] > fit['id']:     # opened while fit's span was open
                assert fit['t0'] <= r['t0'] <= fit['t0'] + fit['dur']
            else:
                assert r is fit or r['name'].startswith(
                    ('trainer.new', 'trainer.init', 'jax.'))
    # nothing went to the gated buffers
    snapshot = ring.metrics_snapshot()
    assert snapshot['buffered_spans'] == 0 and snapshot['spans'] == {}
    # steps are numbered over the trainer's life, not per call of fit
    tr.fit(state, [batch_of(32)], prefetch=0)
    assert names(ring.loop_records())[-5:] == [
        ('trainer.input', 4), ('trainer.step', 4),
        ('trainer.loss_readback', 4), ('trainer.input', 5),
        ('trainer.fit', 4)]


def test_no_span_takes_a_name_the_benchmark_reads(ring):
    """``benchmark/trace_reduce.py`` counts the host spans named
    ``fit.step`` as traced steps and keeps ``data.next``: a program span
    of either name would halve every per-step metric."""
    tr = tiny_trainer()
    state = tr.init(jax.random.PRNGKey(0))
    tr.compile_step(state, batch_of(32))
    tr.fit(state, [batch_of(32)] * 2, eval_data=[batch_of(32)], prefetch=1)
    seen = {r['name'] for r in ring.loop_records()}
    assert {name for name in seen if not name.startswith('jax.')} == {
        'trainer.new', 'trainer.init', 'trainer.init.params',
        'trainer.init.opt_state', 'trainer.init.place',
        'trainer.compile_step', 'trainer.compile_step.build',
        'trainer.compile_step.place', 'trainer.compile_step.lower',
        'trainer.compile_step.compile',
        'trainer.new_step_signature', 'trainer.fit',
        'trainer.input', 'trainer.source', 'trainer.place',
        'trainer.step', 'trainer.loss_readback', 'trainer.eval'}
    assert {name for name in seen if name.startswith('jax.')} <= set(
        core.JAX_DURATIONS.values())
    import autodist_tpu
    for path in glob.glob(autodist_tpu.__path__[0] + '/**/*.py',
                          recursive=True):
        with open(path) as f:
            text = f.read()
        assert "'fit.step'" not in text and "'data.next'" not in text, path


def test_the_ring_stays_bounded(ring):
    for i in range(core.LOOP_RING + 100):
        with ring.loop_span('trainer.step', step=i):
            pass
    records = ring.loop_records()
    assert len(records) == core.LOOP_RING
    assert records[0]['step'] == 100 and records[-1]['step'] == \
        core.LOOP_RING + 99


def test_set_up_records_outlive_the_ring(ring):
    tr = tiny_trainer()
    state = tr.init(jax.random.PRNGKey(0))
    tr.compile_step(state, batch_of(32))
    kept = [r for r in ring.loop_records()
            if r['name'].startswith(('trainer.', 'jax.'))]
    assert len(kept) >= 13
    for i in range(2000):
        ring.loop_event('trainer.counters', step=i)
    records = ring.loop_records()
    assert len(records) == len(kept) + core.LOOP_RING
    assert records[:len(kept)] == kept
    # and the list of them is bounded: past its cap a set-up record
    # takes its chance in the ring like any other
    for i in range(core.LOOP_SETUP):
        with ring.loop_span('trainer.new', setup=True):
            pass
    assert len(ring.loop_records()) == core.LOOP_SETUP + core.LOOP_RING


def test_every_record_names_the_span_that_caused_it(ring):
    tr = tiny_trainer()
    state = tr.init(jax.random.PRNGKey(0))
    tr.compile_step(state, batch_of(32))
    tr.fit(state, iter([batch_of(32, seed=i) for i in range(5)]), steps=3,
           prefetch=2)
    records = ring.loop_records()
    ids = [r['id'] for r in records]
    assert len(set(ids)) == len(ids)
    name_of = {r['id']: r['name'] for r in records}
    parents = {}
    for r in records:
        parents.setdefault(r['name'], set()).add(name_of.get(r['parent']))
    assert parents['trainer.new'] == parents['trainer.init'] == \
        parents['trainer.compile_step'] == parents['trainer.fit'] == {None}
    for child in ('params', 'opt_state', 'place'):
        assert parents['trainer.init.' + child] == {'trainer.init'}
    for child in ('build', 'place', 'lower', 'compile'):
        assert parents['trainer.compile_step.' + child] == {
            'trainer.compile_step'}
    assert parents['trainer.new_step_signature'] == {
        'trainer.compile_step.build'}
    assert parents['trainer.source'] == parents['trainer.place'] == {
        'trainer.input'}
    for name in ('trainer.input', 'trainer.step', 'trainer.loss_readback'):
        assert parents[name] == {'trainer.fit'}
    # what JAX timed sits under the span that made it do the work
    lowered = by_name(records, 'jax.lower')
    assert 'trainer.compile_step.lower' in {
        name_of[r['parent']] for r in lowered
        if r['tags']['fun_name'] == 'jit(step_fn)'}
    assert {name_of[r['parent']] for r in by_name(
        records, 'jax.backend_compile')
        if r['tags']['fun_name'] == 'jit(step_fn)'} == {
            'trainer.compile_step.compile'}
    # a span's children lie inside it, so its self time is its duration
    # less theirs
    spans = {r['id']: r for r in records if r['dur'] is not None}
    for r in records:
        if r['parent'] is not None:
            p = spans[r['parent']]
            assert p['t0'] <= r['t0'] and \
                r['t0'] + (r['dur'] or 0) <= p['t0'] + p['dur'] + 1e-6
    assert by_name(records, 'trainer.init.place')[0]['tags']['leaves'] == \
        len(jax.tree.leaves(state.opt_state))


def test_two_trainers_are_told_apart(ring):
    first = tiny_trainer()
    second = Trainer(first.model, optax.sgd(1.0), spec=ParallelSpec(dp=1),
                     donate=False)
    assert second._tag == first._tag + 1
    state = first.init(jax.random.PRNGKey(0))
    second.step(second.init(None, params=state.params), batch_of(32))
    first.fit(state, [batch_of(32)], prefetch=1)
    tags = {}
    for r in ring.loop_records():
        if r['name'].startswith('trainer.'):
            tags.setdefault(r['tags']['trainer'], set()).add(r['name'])
    assert tags[second._tag] == {
        'trainer.new', 'trainer.init', 'trainer.init.params',
        'trainer.init.opt_state', 'trainer.init.place',
        'trainer.new_step_signature', 'trainer.step'}
    assert tags[first._tag] >= {'trainer.new', 'trainer.init', 'trainer.fit',
                                'trainer.source', 'trainer.place',
                                'trainer.input', 'trainer.step'}


def test_jax_says_what_a_compile_took_under_the_span_that_caused_it(
        ring, monkeypatch):
    monkeypatch.setattr(core, 'MIN_TRACE_S', 0.0)

    def fresh(x):
        return x * 3 + 1

    with ring.loop_span('outer') as outer:
        jax.jit(fresh)(jnp.ones(4))
    records = ring.loop_records()
    mine = [r for r in records
            if r.get('tags', {}).get('fun_name') in ('fresh', 'jit(fresh)')]
    assert [r['name'] for r in mine] == ['jax.trace', 'jax.lower',
                                         'jax.backend_compile']
    span = by_name(records, 'outer')[0]
    for r in mine:
        assert r['parent'] == outer.id == span['id']
        assert r['dur'] > 0 and span['t0'] <= r['t0'] and \
            r['t0'] + r['dur'] <= span['t0'] + span['dur']
    assert [r['t0'] for r in mine] == sorted(r['t0'] for r in mine)
    # the listener is one a process, whatever registry is current
    telemetry.reset()
    telemetry.reset()
    with telemetry.get().loop_span('outer'):
        jax.jit(lambda x: fresh(x) - 2)(jnp.ones(4))
    again = [r['name'] for r in telemetry.get().loop_records()
             if r.get('tags', {}).get('fun_name') in ('<lambda>',
                                                       'jit(<lambda>)')]
    assert again == ['jax.trace', 'jax.lower', 'jax.backend_compile']
    assert not by_name(ring.loop_records(), 'jax.lower')[len(
        by_name(records, 'jax.lower')):]
    # the traces JAX reports from inside another trace are left out
    monkeypatch.undo()
    telemetry.reset()
    jax.jit(lambda x: jnp.add(x, 1) * 2)(jnp.ones(4))
    assert not [r for r in telemetry.get().loop_records()
                if r['name'] == 'jax.trace' and r['dur'] < core.MIN_TRACE_S]


def test_a_second_batch_shape_in_fit_says_how_long_it_compiled(ring):
    tr = tiny_trainer()
    state = tr.init(jax.random.PRNGKey(0))
    tr.fit(state, [batch_of(32), batch_of(32), batch_of(16), batch_of(16)],
           prefetch=1)
    records = ring.loop_records()
    steps = {r['id']: r['step'] for r in by_name(records, 'trainer.step')}
    compiled = [steps[r['parent']]
                for r in by_name(records, 'jax.backend_compile')
                if r['parent'] in steps
                and r['tags']['fun_name'] == 'jit(step_fn)']
    # (step 2 compiles as well, today: ``init`` hands ``step`` an
    # uncommitted counter and the step hands back a committed one, so
    # jit sees a second signature; found by these records, PERF.md §7)
    assert set(compiled) - {2} == {1, 3}
    assert [r['step'] for r in by_name(
        records, 'trainer.new_step_signature')] == [1, 3]


def test_a_new_batch_shape_says_which_step_recompiled(ring):
    tr = tiny_trainer()
    state = tr.init(jax.random.PRNGKey(0))
    for seq in (32, 32, 16, 16, 32):
        state, _ = tr.step(state, batch_of(seq))
    events = [r for r in ring.loop_records()
              if r['name'] == 'trainer.new_step_signature']
    assert [e['step'] for e in events] == [1, 3]
    assert '(4, 32)' in events[0]['tags']['shapes']
    assert '(4, 16)' in events[1]['tags']['shapes']


def test_with_telemetry_on_the_loop_spans_are_exported_too(monkeypatch):
    monkeypatch.setenv('AUTODIST_TELEMETRY', '1')
    telemetry.reset()
    try:
        tel = telemetry.get()
        with tel.loop_span('trainer.step', step=7):
            pass
        tel.loop_event('trainer.new_step_signature', step=7, shapes='x')
        assert len(tel.loop_records()) == 2
        span, event = tel.drain_spans()
        assert span['name'] == 'trainer.step' and span['tags'] == {'step': 7}
        assert event['name'] == 'trainer.new_step_signature'
        assert event['tags'] == {'shapes': 'x', 'step': 7}
    finally:
        telemetry.reset()


def test_profile_goes_through_step_with_the_python_tracer_off(ring, tmp_path):
    from jax.profiler import ProfileData
    tr = tiny_trainer()
    state = tr.init(jax.random.PRNGKey(0))
    out = tr.profile(state, batch_of(32), str(tmp_path / 'trace'), steps=2)
    assert [n for n in names(ring.loop_records())
            if n[0] == 'trainer.step'] == [('trainer.step', 1),
                                           ('trainer.step', 2),
                                           ('trainer.step', 3)]
    found = glob.glob(out + '/**/*.xplane.pb', recursive=True)
    assert len(found) == 1
    host = [e.name for plane in ProfileData.from_file(found[0]).planes
            if plane.name == '/host:CPU'
            for line in plane.lines for e in line.events]
    # the two traced steps' spans are on the trace; no Python frames
    assert host.count('trainer.step') == 2
    assert not any(name.startswith('$') for name in host)
