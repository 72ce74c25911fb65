"""``setup_reduce`` (PR 35): the seven ``setup_*`` readers and
``host_gap_counters_ms`` on hand-built records, what each says where
there is nothing to read, and the rehearsal's real ring."""
import importlib
import json
import sys
import types

import pytest

from bench_paths import tiny_config
from test_benchmark_scopes import HAND, RECORDS, RUN, T0, text_proto

from benchmark import harness, setup_reduce, span_reduce
from benchmark import trace_reduce as tr

SETUP_METRICS = ('setup_before_trainer_s', 'setup_init_s',
                 'setup_init_place_s', 'setup_step_lower_s',
                 'setup_step_compile_s', 'setup_jax_trace_lower_s',
                 'setup_cache_retrieval_s')

T_START = 100.0


def rec(id_, name, start, dur, parent=None, trainer=None, **tags):
    """A ring record ``start`` seconds after ``T_START``."""
    if trainer is not None:
        tags['trainer'] = trainer
    out = {'name': name, 't0': T_START + start, 'dur': dur, 'step': None,
           'id': id_, 'parent': parent}
    if tags:
        out['tags'] = tags
    return out


# Trainer 0 trains, Trainer 1 is the harness's probe. Seconds after the
# command's first line:
#   12.0-12.5   trainer.new
#   12.5-18.0   trainer.init = params 12.5-15 + opt_state 15-16 + place
#               16-17.9 + 0.1 of its own; jit(init) traced 12.6-13.6 with
#               a function traced inside it 12.8-13.0, lowered 13.6-14.1
#   18.0-28.0   trainer.compile_step = build 18-18.1 + place 18.1-18.2 +
#               lower 18.2-24 + compile 24-28 (a cache hit: retrieval
#               25-27 inside the request 24.1-27.9)
#   28.0-40.0   the probe: new, init, a step that traces 30-34 and lowers
#               34-36
#   41.0-43.0   the warm-up's fit
#   50.0-70.0   the measured fit; a step in it recompiles (51-53)
RING = [
    rec(0, 'trainer.new', 12.0, 0.5, trainer=0),
    rec(3, 'jax.trace', 12.8, 0.2, parent=2, fun_name='_normal'),
    rec(4, 'jax.trace', 12.6, 1.0, parent=2, fun_name='init'),
    rec(5, 'jax.lower', 13.6, 0.5, parent=2, fun_name='jit(init)'),
    rec(2, 'trainer.init.params', 12.5, 2.5, parent=1, trainer=0),
    rec(6, 'trainer.init.opt_state', 15.0, 1.0, parent=1, trainer=0),
    rec(7, 'trainer.init.place', 16.0, 1.9, parent=1, trainer=0, leaves=29),
    rec(1, 'trainer.init', 12.5, 5.5, trainer=0),
    rec(10, 'trainer.new_step_signature', 18.05, None, parent=9, trainer=0),
    rec(9, 'trainer.compile_step.build', 18.0, 0.1, parent=8, trainer=0),
    rec(11, 'trainer.compile_step.place', 18.1, 0.1, parent=8, trainer=0),
    rec(13, 'jax.trace', 18.3, 3.0, parent=12, fun_name='step_fn'),
    rec(14, 'jax.lower', 21.3, 2.6, parent=12, fun_name='jit(step_fn)'),
    rec(12, 'trainer.compile_step.lower', 18.2, 5.8, parent=8, trainer=0),
    rec(16, 'jax.cache_retrieval', 25.0, 2.0, parent=15),
    rec(17, 'jax.backend_compile', 24.1, 3.8, parent=15,
        fun_name='jit(step_fn)'),
    rec(15, 'trainer.compile_step.compile', 24.0, 4.0, parent=8, trainer=0),
    rec(8, 'trainer.compile_step', 18.0, 10.0, trainer=0),
    rec(18, 'trainer.new', 28.0, 0.1, trainer=1),
    rec(19, 'trainer.init', 28.1, 1.9, trainer=1),
    rec(21, 'jax.trace', 30.0, 4.0, parent=20, fun_name='step_fn'),
    rec(22, 'jax.lower', 34.0, 2.0, parent=20, fun_name='jit(step_fn)'),
    rec(20, 'trainer.step', 30.0, 10.0, trainer=1),
    rec(24, 'trainer.step', 41.1, 0.1, parent=23, trainer=0),
    rec(23, 'trainer.fit', 41.0, 2.0, trainer=0),
    rec(27, 'jax.trace', 51.0, 2.0, parent=26, fun_name='step_fn'),
    rec(26, 'trainer.step', 50.5, 3.0, parent=25, trainer=0),
    rec(25, 'trainer.fit', 50.0, 20.0, trainer=0),
]
WANTED = {
    'setup_before_trainer_s': 12.0,
    'setup_init_s': 5.5,
    'setup_init_place_s': 1.9,
    'setup_step_lower_s': 5.8,
    'setup_step_compile_s': 4.0,
    # 12.6-14.1 (the inner trace counts once), 18.3-23.9, 30-36; not the
    # window's 51-53
    'setup_jax_trace_lower_s': 1.5 + 5.6 + 6.0,
    'setup_cache_retrieval_s': 2.0,
}


@pytest.fixture
def on_a_chip(monkeypatch):
    """A trace with a device plane, the command's clock, and ``RING`` as
    the program's ring; returns the list the readers say into."""
    monkeypatch.setitem(sys.modules, '__main__',
                        types.SimpleNamespace(T_START=T_START))
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: list(RING))
    return []


def metric(name, said, trace=None, **run):
    module = importlib.import_module('benchmark.layer_metrics.' + name)
    trace = tr.Trace(ops={0: []}) if trace is None else trace
    return module.reduce(trace, dict(run, say=said.append))


@pytest.mark.parametrize('name', SETUP_METRICS)
def test_setup_metric_on_hand_built_records(on_a_chip, name):
    assert metric(name, on_a_chip) == pytest.approx(WANTED[name])


def test_set_up_ends_where_the_measured_fit_starts():
    setup, trainer = setup_reduce.setup_of(RING)
    assert trainer == 0
    assert [r['id'] for r in RING if r not in setup] == [27, 26, 25]
    # the probe's records are set-up, but no span of the Trainer's
    assert setup_reduce.span_s(setup, 0, 'trainer.step') == \
        pytest.approx(0.1)
    assert setup_reduce.span_s(setup, 1, 'trainer.step') == \
        pytest.approx(10.0)
    assert setup_reduce.span_s(setup, 0, 'trainer.save') is None


def test_a_spans_self_time_is_its_duration_less_its_children(on_a_chip):
    setup, trainer = setup_reduce.setup_of(RING)
    by_name = {r['name']: r for r in setup if r.get('tags', {}).get(
        'trainer') == 0 and r['name'] != 'trainer.fit'}
    assert setup_reduce.self_s(by_name['trainer.init'], setup) == \
        pytest.approx(0.1)
    assert setup_reduce.self_s(by_name['trainer.compile_step'], setup) == \
        pytest.approx(0.0)
    assert setup_reduce.self_s(by_name['trainer.init.params'], setup) == \
        pytest.approx(2.5 - 1.5)
    # the cache's retrieval lies inside the compile request: once
    assert setup_reduce.self_s(
        by_name['trainer.compile_step.compile'], setup) == pytest.approx(0.2)
    # the first reader says the whole table, once, as one JSON object
    said = on_a_chip
    for name in SETUP_METRICS:
        metric(name, said)
    tables = [line for line in said if 'loop records before' in line]
    assert len(tables) == 1 and tables[0].startswith(
        'set-up from inside: trainer 0, 25 of 28 loop records')
    table = json.loads(tables[0].split(': ', 2)[2])
    assert table['spans']['trainer.init'] == [1, 5.5, 0.1]
    assert table['spans']['trainer.fit'] == [1, 2.0, 1.9]
    assert table['under'] == {
        'trainer.init.params': {'jax.trace': 1.0, 'jax.lower': 0.5},
        'trainer.compile_step.lower': {'jax.trace': 3.0, 'jax.lower': 2.6},
        'trainer.compile_step.compile': {'jax.cache_retrieval': 2.0,
                                         'jax.backend_compile': 3.8}}
    assert table['jax'] == {'jax.trace': [4, 8.0], 'jax.lower': [3, 5.1],
                            'jax.cache_retrieval': [1, 2.0],
                            'jax.backend_compile': [1, 3.8]}


def test_a_cold_run_retrieved_nothing_from_the_cache(on_a_chip, monkeypatch):
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: [
        r for r in RING if r['name'] != 'jax.cache_retrieval'])
    assert metric('setup_cache_retrieval_s', on_a_chip) == 0
    assert metric('setup_jax_trace_lower_s', on_a_chip) == pytest.approx(
        WANTED['setup_jax_trace_lower_s'])


def test_no_clock_to_count_from(monkeypatch, on_a_chip):
    monkeypatch.setitem(sys.modules, '__main__', types.SimpleNamespace())
    assert metric('setup_before_trainer_s', on_a_chip) is None
    assert 'has no T_START' in on_a_chip[-1]
    assert metric('setup_init_s', on_a_chip) == pytest.approx(5.5)


@pytest.mark.parametrize('name', SETUP_METRICS)
def test_a_program_without_such_spans_reads_as_nothing(on_a_chip,
                                                       monkeypatch, name):
    said = on_a_chip
    # the parent's ring: flat records, no id, no parent, no trainer tag
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: RECORDS)
    assert metric(name, said) is None
    assert 'no loop record carries the tag `trainer`' in said[-1]
    # a program with no ring at all
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: None)
    assert metric(name, said) is None
    assert 'has no loop ring' in said[-1]
    # a Trainer that never fitted; one whose set-up made no such record
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: RING[:18])
    assert metric(name, said) is None
    assert 'no trainer.fit record of trainer 0' in said[-1]
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: [
        r for r in RING if r['name'] == 'trainer.fit'])
    assert metric(name, said) is None
    assert 'no record for this reader' in said[-1]
    # the CPU's trace has no device plane: its timings are no chip's
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: list(RING))
    assert metric(name, said, trace=tr.Trace()) is None
    assert 'no device plane' in said[-1]


# -- host_gap_counters_ms on test_benchmark_scopes' hand-built trace --------

def test_counters_readback_is_a_part_of_the_unattributed_gap(monkeypatch):
    from jax.profiler import ProfileData
    hand = tr.load(ProfileData.from_text_proto(text_proto(HAND)))
    # between a read-back's end and the next input's start (5100-5150,
    # 10150-10200 on the trace's clock), where both chips are idle: 40 ns
    # of each gap, two steps
    counters = [{'name': 'trainer.counters_readback',
                 't0': T0 + start * 1e-9, 'dur': 40e-9, 'step': 1}
                for start in (5105, 10155)]
    monkeypatch.setattr(span_reduce, 'ring_records',
                        lambda: RECORDS + counters)
    said = []
    value = metric('host_gap_counters_ms', said, trace=hand, **RUN)
    assert value == pytest.approx(40e-6, rel=1e-4)
    assert value <= metric('host_gap_unattributed_ms', said, trace=hand,
                           **RUN)
    # a model that counts nothing has no such span; the CPU no device
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: RECORDS)
    assert metric('host_gap_counters_ms', said, trace=hand, **RUN) is None
    assert '0 such spans' in said[-1]
    assert metric('host_gap_counters_ms', said, trace=tr.Trace(),
                  **RUN) is None
    assert 'no device plane' in said[-1]


# -- the real ring, after a rehearsal ----------------------------------------

def test_traced_rehearsal_reports_what_it_did_and_leaves_a_ring_to_read(
        tmp_path, monkeypatch):
    from autodist_tpu import telemetry
    telemetry.reset()           # other tests' Trainers are not this run's
    cell = dict(name='bert-large.s512.c1', config='tiny', traffic='tiny',
                chips=1, engine='trainer', parallel={'dp': 1}, trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    traffic = dict(generator='zipf_lm', seq=32, global_batch=4,
                   zipf_exponent=1.1)
    try:
        result, lines = harness.rehearse(
            cell, tiny_config(causal=False), traffic,
            {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}, seed=3,
            trace=True, out_dir=str(tmp_path))
        # on the CPU the new readers report nothing, and say so
        assert set(result['metrics']) == {
            'compile_s', 'compile_cache_miss', 'step_wall_ms', 'step_hbm_gb'}
        assert sum('set-up from inside: the trace has no device plane'
                   in line for line in lines) == len(SETUP_METRICS) - 1
        assert sum('has no T_START' in line for line in lines) == 1
        # the ring the run left is a real program's: read it as on a chip
        records = span_reduce.ring_records()
        setup, trainer = setup_reduce.setup_of(records)
        probe = {r['tags']['trainer'] for r in records
                 if r['name'] == 'trainer.new'} - {trainer}
        assert len(probe) == 1 and min(probe) > trainer
        report = json.loads(lines[-1])['setup_phases_s']
        said = []
        monkeypatch.setitem(sys.modules, '__main__', types.SimpleNamespace(
            T_START=min(r['t0'] for r in records)))
        value = {name: metric(name, said) for name in SETUP_METRICS}
        assert 0 < value['setup_init_place_s'] < value['setup_init_s'] \
            <= report['init']
        assert value['setup_step_lower_s'] + value['setup_step_compile_s'] \
            == pytest.approx(report['compile_step'], abs=0.05)
        # JAX's part of the lowering is inside the span, and the probe's
        # and the reference's tracing beside it in the sum
        assert value['setup_jax_trace_lower_s'] > 0.5 * \
            value['setup_step_lower_s']
        assert value['setup_before_trainer_s'] >= 0
        assert value['setup_cache_retrieval_s'] == 0       # no cache here
        # the measured fit's records are the ring's, not set-up's
        assert {r['name'] for r in records if r not in setup} >= {
            'trainer.fit', 'trainer.step', 'trainer.loss_readback'}
        fits = [r for r in setup if r['name'] == 'trainer.fit']
        assert len(fits) == 1 and fits[0]['tags']['steps'] == \
            harness.WARMUP_STEPS
    finally:
        telemetry.reset()
