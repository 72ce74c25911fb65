"""Family ``nemotron_h`` (PR 41), the cell: its own comparison through the
engine and the harness at its own limits, its files, a rehearsal of its
run loop on the CPU and of its metrics' readers on a step recorded on the
chip. The program against the reference, the shares and the hand counts
are in ``test_benchmark_nemotron_h.py``, whose tiny configuration and
faults these tests share; the two are files apart so that the suite's
workers share them."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench_paths import BENCH, benchmark_json
from test_benchmark_nemotron_h import (CELL, FAULTS, LEAF_RTOL,  # noqa: F401
                                       PEAKS, TRAFFIC, case, reference_grads,
                                       tiny_config)

from benchmark import harness
from benchmark.models import nemotron_h


@pytest.fixture(scope='module')
def probed(case):
    """The program's side of the cell's own comparison, once: the probe
    of ``trainer_leaves`` on two sequences (loss, global norm, and the
    gradient it left for the family)."""
    from benchmark.engines import trainer_leaves
    config, _, params, probe, _ = case
    engine = trainer_leaves.Engine(nemotron_h.build(config), {'dp': 1},
                                   jax.devices()[:1])
    state = engine.trainer.init(None, params=params)
    got = engine.loss_and_grad_norm(state, probe)
    assert set(trainer_leaves.PROBE) == {'gradients'}
    return probe, got, trainer_leaves.PROBE.pop('gradients')


def through_the_harness(config, params, probed, **switches):
    """``(checks pass, reference)`` of the cell's own comparison: the
    family's reference beside the probe's gradient, under
    ``harness.close`` at the harness's limits."""
    from benchmark.engines import trainer_leaves
    probe, got, gradients = probed
    trainer_leaves.PROBE['gradients'] = gradients
    want = nemotron_h.reference_loss_and_grad_norm(
        config, nemotron_h.to_reference_params(params), probe, **switches)
    assert 'gradients' not in trainer_leaves.PROBE     # taken, not left
    return (harness.close(got[0], want[0], harness.LOSS_RTOL),
            harness.close(got[1], want[1], harness.GRAD_NORM_RTOL)), want


@pytest.mark.parametrize('broken', ['sound'] + sorted(FAULTS)
                         + ['fp8_products'])
def test_the_cells_own_comparison_catches_each_fault(case, probed, capsys,
                                                     broken):
    """Through the engine and the family as ``harness.py`` calls them,
    at the cell's own limits: the sound reference passes and each
    deliberate fault of ISSUE 41's list fails ``reference_grad_norm``."""
    config, _, params, _, _ = case
    switches = {} if broken == 'sound' else FAULTS.get(broken) or dict(
        matmul_dtype=jnp.float8_e4m3fn)
    (loss_ok, norm_ok), want = through_the_harness(config, params, probed,
                                                   **switches)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line['limits'] == {'leaf': nemotron_h.LEAF_RTOL,
                              'routed_leaf': nemotron_h.ROUTED_LEAF_RTOL,
                              'router_leaf': nemotron_h.ROUTER_LEAF_RTOL}
    leaves = line['gradient_leaves']
    # embed, ln_final, head; 9 a Mamba-2 layer of four, 7 an expert
    # layer of four, 5 the attention layer
    assert len(leaves) == 3 + 4 * 9 + 4 * 7 + 5
    assert leaves['layer_1/b_select'] == leaves['layer_8/b_select'] == 0
    assert nemotron_h.leaf_limit('layer_1/w_down') \
        == nemotron_h.ROUTED_LEAF_RTOL == nemotron_h.leaf_limit(
            'layer_3/ln_mlp')
    assert nemotron_h.leaf_limit('layer_6/w_router') \
        == nemotron_h.ROUTER_LEAF_RTOL
    assert nemotron_h.leaf_limit('layer_0/w_in') == nemotron_h.LEAF_RTOL \
        == nemotron_h.leaf_limit('layer_1/ws_down') \
        == nemotron_h.leaf_limit('layer_5/w_k')
    assert want[1] == pytest.approx(line['reference_global_grad_norm'] * (
        1 + harness.GRAD_NORM_RTOL * line['worst_in_limits']), rel=1e-9)
    assert norm_ok is (broken == 'sound')
    if broken == 'sound':
        assert loss_ok and line['worst_difference'] < LEAF_RTOL


def test_a_selection_bias_with_a_gradient_is_a_thousand_limits(case):
    config, _, params, probe, (_, got_grads) = case
    _, want = reference_grads(config, nemotron_h.to_reference_params(params),
                              probe)
    got = dict(got_grads, layer_1=dict(got_grads['layer_1'],
                                       b_select=jnp.full((16,), 1e-9)))
    norm = nemotron_h.held_to_every_leaf(1.0, got, want, 1)
    assert norm == pytest.approx(1 + harness.GRAD_NORM_RTOL * 1e3)


def test_the_cells_files_say_what_issue_41_asks():
    bench = benchmark_json()
    cell = [w for w in bench['workloads'] if w['name'] == CELL]
    assert cell == [dict(cell[0], config='nemotron-3-nano-30b-a3b',
                         traffic='clm-s8192-gb2-z05', chips=1)]
    workload = harness.load_json('workloads', CELL)
    assert (workload['engine'], workload['parallel'],
            workload['trace_steps'], workload['expects']) == (
        'trainer_leaves', {'dp': 1}, 17,
        {'pallas_custom_calls': True, 'collectives': []})
    assert workload['why'] == cell[0]['why']
    traffic = harness.load_json('traffic', workload['traffic'])
    assert (traffic['generator'], traffic['seq'], traffic['global_batch'],
            traffic['zipf_exponent']) == ('zipf_lm', 8192, 2, 0.5)
    mine = {m['name'] for m in bench['per_layer']
            if m.get('workloads') == [CELL]}
    assert mine == {
        'ssm_ms_per_step', 'ssm_mixer_ms_per_step', 'ssd_scan_ms_per_step',
        'ssd_scan_fwd_roofline_pct', 'ssd_scan_bwd_roofline_pct',
        'moe_layer_ms_per_step', 'moe_layer_rows_here_pct',
        'moe_layer_load_max_over_mean'}
    for name in mine:
        module = harness.load_module('layer_metrics', name)
        entry = next(m for m in bench['per_layer'] if m['name'] == name)
        assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
                module.MOVES) == (entry['layer'], entry['unit'],
                                  entry['better'], entry['source'],
                                  entry['moves'])


@pytest.mark.parametrize('trace', [False, True], ids=['untraced', 'traced'])
def test_rehearsal_of_the_cell(tmp_path, trace):
    """The run loop with the new family at the tiny size on the CPU,
    under the real cell's name so that ``BENCHMARK.json``'s lists apply."""
    cell = dict(name=CELL, config='tiny', traffic='tiny', chips=1,
                engine='trainer_leaves', parallel={'dp': 1}, trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    # (ids Zipf(2) here: at 256 tokens a step and ten steps the loss of
    # a flatter law falls inside its own scatter)
    # six layers of the nine, MEMEM*: a layer of each kind, two thirds
    # of the compile
    result, lines = harness.rehearse(
        cell, tiny_config(num_hidden_layers=6),
        dict(TRAFFIC, zipf_exponent=2.0), PEAKS,
        seed=2147483693, trace=trace, out_dir=str(tmp_path))
    report = json.loads(lines[-1])
    assert result['correct'] is True, report['checks']
    assert result['device']['platform'] == 'cpu'
    bench = benchmark_json()
    if trace:
        # no device plane on the CPU: the readers of the trace find
        # nothing and say so without raising; the counters are the
        # program's and are read here as on the chip
        assert set(result['metrics']) == {
            'compile_s', 'compile_cache_miss', 'step_wall_ms', 'step_hbm_gb',
            'moe_layer_rows_here_pct', 'moe_layer_load_max_over_mean'}
        listed = harness.metrics_for(CELL, bench['per_layer'])
        assert {'ssm_ms_per_step', 'ssm_mixer_ms_per_step',
                'ssd_scan_ms_per_step', 'ssd_scan_fwd_roofline_pct',
                'ssd_scan_bwd_roofline_pct', 'moe_layer_ms_per_step'} \
            <= set(listed)
        assert 'moe_rows_here_pct' not in listed
        assert 'flash_mla_ms_per_step' not in listed
        # the counters count the two expert layers, not the other four
        assert 0 < result['metrics']['moe_layer_rows_here_pct']['value'] \
            <= 100
        assert 1 <= result['metrics'][
            'moe_layer_load_max_over_mean']['value'] <= 4
    else:
        assert set(result['metrics']) == {m['name']
                                          for m in bench['end_to_end']}


# benchmark/testdata/ssm/<cell>.step.textproto.gz is one traced step of
# the cell's run on the chip (a directory of its own: test_benchmark_trace
# takes every trace of benchmark/testdata itself for its brute-force
# fixtures); the .json beside it holds the compiled step's lines for
# that step's operations and what the run's own result line read.
STORED = os.path.join(BENCH, 'testdata', 'ssm', CELL + '.step')
READ_FROM_THE_TRACE = (
    'device_step_ms', 'optimizer_ms_per_step', 'attention_ms_per_step',
    'mlp_ms_per_step', 'head_loss_ms_per_step', 'flash_ms_per_step',
    'ssm_ms_per_step', 'ssm_mixer_ms_per_step', 'ssd_scan_ms_per_step',
    'ssd_scan_fwd_roofline_pct', 'ssd_scan_bwd_roofline_pct',
    'moe_layer_ms_per_step')


@pytest.fixture(scope='module')
def stored():
    from benchmark import trace_reduce as tr
    with open(STORED + '.json') as f:
        facts = json.load(f)
    cell = harness.load_json('workloads', CELL)
    said = []
    run = {'cell': cell, 'config': harness.load_json('configs',
                                                     cell['config']),
           'traffic': harness.load_json('traffic', cell['traffic']),
           'chips': 1, 'peaks': harness.load_peaks(facts['device']['kind']),
           'hlo': '\n'.join(facts['hlo_lines']), 'say': said.append}
    return tr.load_file(STORED + '.textproto.gz'), run, facts, said


@pytest.mark.parametrize('name', READ_FROM_THE_TRACE)
def test_rehearsal_of_the_cells_metrics_from_a_stored_trace(stored, name):
    """Each reader of the cell's device metrics on one step recorded on
    the chip reads what the run itself read over its 17 traced steps (a
    device step repeats to 0.1%)."""
    trace, run, facts, _ = stored
    assert facts['device']['kind'] == 'TPU v5 lite' and trace.steps == 1
    got = harness.load_module('layer_metrics', name).reduce(trace, run)
    assert got == pytest.approx(facts['full_run'][name], rel=2e-3)


def test_the_stored_step_is_accounted_for_by_its_four_scopes(stored):
    """ISSUE 41's account: ``attention_`` + ``mlp_`` + ``ssm_`` +
    ``head_loss_ms_per_step`` within 5% of ``device_step_ms`` less the
    optimizer; the scan's kernels are a part of ``ssm`` and no part of
    ``ssm_mixer``; 8 forward calls (4 run again under the blocks'
    checkpoint) and 4 backward; no share of a roofline over 100%."""
    trace, run, _, said = stored

    def read(name):
        return harness.load_module('layer_metrics', name).reduce(trace, run)
    parts = sum(read(n) for n in (
        'attention_ms_per_step', 'mlp_ms_per_step', 'ssm_ms_per_step',
        'head_loss_ms_per_step'))
    rest = read('device_step_ms') - read('optimizer_ms_per_step')
    assert 0.95 * rest <= parts <= rest
    assert read('ssm_mixer_ms_per_step') + read('ssd_scan_ms_per_step') \
        <= read('ssm_ms_per_step')
    assert read('moe_layer_ms_per_step') <= read('mlp_ms_per_step')
    assert 0 < read('ssd_scan_bwd_roofline_pct') \
        < read('ssd_scan_fwd_roofline_pct') < 100
    assert any(line.startswith('ssd_fwd:') and ' in 8 calls' in line
               for line in said)
    assert any(line.startswith('ssd_bwd:') and ' in 4 calls' in line
               for line in said)
