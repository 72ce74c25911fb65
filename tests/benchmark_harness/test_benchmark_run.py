"""The run loop: a tiny-width rehearsal on the CPU's virtual devices,
the last line's form, and the refusal to run without a chip."""
import json

import numpy as np
import pytest

from bench_paths import benchmark_json, tiny_config

from benchmark import harness

PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
DEVICE_KEYS = {'platform', 'kind', 'count', 'memory_peak_bytes'}


def rehearse(tmp_path, chips, trace, causal, **cell_keys):
    # the rehearsal borrows a real cell's name so that BENCHMARK.json's
    # metric lists apply; every size is tiny and the platform is the CPU
    name = 'bert-large.s512.dp4' if chips == 4 else 'bert-large.s512.c1'
    cell = dict(name=name, config='tiny', traffic='tiny', chips=chips,
                engine='trainer', parallel={'dp': chips}, trace_steps=3,
                expects={'pallas_custom_calls': False,
                         'collectives': ['all-reduce'] if chips > 1 else []},
                **cell_keys)
    traffic = dict(generator='zipf_lm', seq=32, global_batch=4 * chips,
                   zipf_exponent=1.1)
    return harness.rehearse(cell, tiny_config(causal), traffic, PEAKS,
                            seed=3, trace=trace, out_dir=str(tmp_path))


@pytest.fixture
def traced_steps(monkeypatch):
    """The ``fit.step`` spans of every trace the run loads, counted."""
    from benchmark import trace_reduce
    counts, load_file = [], trace_reduce.load_file

    def counting(path):
        trace = load_file(path)
        counts.append(trace.steps)
        return trace
    monkeypatch.setattr(trace_reduce, 'load_file', counting)
    return counts


def test_rehearsal_one_device_untraced(tmp_path, capfd):
    result, lines = rehearse(tmp_path, chips=1, trace=False, causal=True)
    assert list(result) == ['correct', 'attempted', 'failed', 'metrics',
                            'device', 'compared']
    # each number compared beside its limit: the last key of the last line
    # and the last lines on standard error
    compared = result['compared']
    assert list(compared) == ['loss_apart', 'grad_norm_apart', 'loss_fall']
    assert compared['loss_apart'][1] == harness.LOSS_RTOL
    assert compared['grad_norm_apart'][1] == harness.GRAD_NORM_RTOL
    assert 0 <= compared['loss_apart'][0] < harness.LOSS_RTOL
    assert compared['loss_fall'][0] > compared['loss_fall'][1] == 0
    assert capfd.readouterr().err.splitlines()[-3:] == [
        'compared %s %r limit %r' % (name, x, limit)
        for name, (x, limit) in compared.items()]
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] == harness.MIN_STEPS
    assert set(result['device']) == DEVICE_KEYS
    assert result['device']['platform'] == 'cpu'       # labelled, not a chip
    bench = benchmark_json()
    assert set(result['metrics']) == {m['name'] for m in
                                      bench['end_to_end']}
    for name, metric in result['metrics'].items():
        assert set(metric) == {'value', 'unit'} and metric['value'] > 0
    report = json.loads(lines[-1])
    assert all(report['checks'].values()), report['checks']
    assert report['compile_requests_in_window'] == 0
    assert len(report['losses']) == harness.MIN_STEPS
    assert json.loads(json.dumps(result)) == result    # one JSON line
    # the report's types are the harness's to keep
    assert all(type(ok) is bool for ok in report['checks'].values())
    assert all(type(x) is float for x in report['reference'].values())
    assert report['compared'] == compared


def test_rehearsal_four_devices_traced(tmp_path, traced_steps):
    result, lines = rehearse(tmp_path, chips=4, trace=True, causal=False)
    assert list(result) == ['correct', 'attempted', 'failed', 'metrics',
                            'device', 'breakdown', 'compared']
    assert result['correct'] is True
    # a cell that says nothing of it: ten steps, the last three traced
    assert result['attempted'] == harness.MIN_STEPS and traced_steps == [3]
    assert set(result['device']) == DEVICE_KEYS | {'busy_s', 'window_s'}
    assert set(result['breakdown']) == {'device_ops', 'idle_gaps'}
    # the CPU has no device plane: only the readers of counters and of
    # the host clock find something, the others return nothing
    assert set(result['metrics']) == {
        'compile_s', 'compile_cache_miss', 'step_wall_ms', 'step_hbm_gb'}
    report = json.loads(lines[-1])
    assert report['hlo']['collectives'] == ['all-reduce']
    assert report['hlo']['params_span_mesh'] is True
    assert result['device']['window_s'] > 0
    assert not (tmp_path / 'trace').exists()           # removed after use


def test_a_cell_may_say_how_many_steps_its_window_has_at_least(
        tmp_path, traced_steps):
    """``min_steps`` in the cell's file: the traced ``fit`` is that long
    and its last ``trace_steps`` steps are the traced ones (the untraced
    window's size is the same ``max`` with the seconds' steps)."""
    result, lines = rehearse(tmp_path, chips=1, trace=True, causal=True,
                             min_steps=13)
    report = json.loads(lines[-1])
    assert result['correct'] is True and result['attempted'] == 13
    assert report['steps'] == len(report['losses']) == 13
    assert traced_steps == [3]
    # the host's intervals the readers are handed are the traced steps'
    assert len(report['step_intervals_ms']) == 13
    assert result['metrics']['step_wall_ms']['value'] > 0


@pytest.mark.parametrize('number', [float, np.float64, np.float32],
                         ids=['float', 'float64', 'float32'])
def test_close_is_a_python_bool_whatever_it_is_handed(number):
    """``numpy.float64 <= numpy.float64`` is a ``numpy.bool``, which
    ``json`` refuses: the run's last line was not written when a family
    handed back a numpy scalar (PR 49's runs, PERF.md section 6)."""
    for a, b, near in ((1.0, 1.001, True), (1.0, 1.1, False),
                       (float('nan'), 1.0, False)):
        for args in ((number(a), number(b)), (a, number(b)),
                     (number(a), b)):
            ok = harness.close(*args, 1e-2)
            assert type(ok) is bool and ok is near
            json.dumps({'ok': ok})


def test_compared_keeps_the_last_line_json():
    """A loss that is not finite leaves ``None`` for its numbers, not a
    ``NaN`` that no strict reader of JSON takes."""
    reference = {'loss': float('nan'), 'reference_loss': 1.0,
                 'grad_norm': 2.0, 'reference_grad_norm': 0.0}
    numbers = harness.compared(reference, [float('inf')] * 10)
    assert numbers == {'loss_apart': [None, harness.LOSS_RTOL],
                       'grad_norm_apart': [None, harness.GRAD_NORM_RTOL],
                       'loss_fall': [None, 0.0]}
    assert 'NaN' not in json.dumps(numbers)
    assert 'loss_fall' not in harness.compared(reference, [1.0] * 9)


def test_no_chip_is_an_error_not_a_fallback(tmp_path):
    with pytest.raises(RuntimeError, match='found only cpu'):
        harness.run_cell('bert-large.s128.c1', seed=1, seconds=1.0,
                         trace=False, t_start=0.0, out_dir=str(tmp_path),
                         say=print)


def test_feed_marks_the_start_of_every_step():
    feed = harness.Feed(list(range(7)))
    assert [next(feed) for _ in range(7)] == list(range(7))
    with pytest.raises(StopIteration):
        next(feed)
    feed.finish()
    # requests 0, 3, 4, 5, 6 open steps 1..5; finish closes the last
    assert len(feed.step_times) == 6
    assert feed.step_times == sorted(feed.step_times)


def test_a_stalled_step_does_not_move_the_median_step():
    # 17 steps of 555 ms, four of them stretched by a busy host: the
    # whole call is 1.3% longer, the median step is what it was
    steps = [0.555] * 17
    for i in (2, 7, 8, 13):
        steps[i] += 0.030
    times = [0.0]
    for s in steps:
        times.append(times[-1] + s)
    assert harness.median_step_s(times) == pytest.approx(0.555, rel=1e-9)
    assert times[-1] / 17 > 0.555 * 1.012
    # a change that slows every step shows in full
    slower = [t * 1.01 for t in times]
    assert harness.median_step_s(slower) == pytest.approx(0.555 * 1.01)


def test_metrics_for_keeps_to_the_listed_cells():
    entries = [{'name': 'a'}, {'name': 'b', 'workloads': ['x']}]
    assert harness.metrics_for('x', entries) == ['a', 'b']
    assert harness.metrics_for('y', entries) == ['a']
