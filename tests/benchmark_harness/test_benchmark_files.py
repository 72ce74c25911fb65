"""Every file of the benchmark loads and names only things that exist,
and ``BENCHMARK.json`` keeps to the contract's form."""
import importlib
import json
import os
import re

import pytest

from bench_paths import BENCH, ROOT, benchmark_json, names

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
BENCHMARK = benchmark_json()
END_TO_END = {m['name']: m for m in BENCHMARK['end_to_end']}
PER_LAYER = {m['name']: m for m in BENCHMARK['per_layer']}
CELLS = {w['name']: w for w in BENCHMARK['workloads']}
CONFIGS = {c['name']: c for c in BENCHMARK['configs']}


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + '.json')) as f:
        return json.load(f)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and '\n' not in text and '\t' not in text


def test_top_level_keys_and_limits():
    assert set(BENCHMARK) == {'command', 'paths', 'run_seconds', 'configs',
                              'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= BENCHMARK['run_seconds'] <= 51
    # a full check of 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (BENCHMARK['run_seconds'] + 60) + 24 * 180 \
        + 1200 <= 43200
    assert 2 <= len(CELLS) <= 24 and len(CELLS) == len(
        BENCHMARK['workloads'])
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 65536
    for path in BENCHMARK['paths']:
        assert os.path.isdir(os.path.join(ROOT, path))
    program = [w for w in BENCHMARK['command'] if w.endswith('.py')]
    assert program and all(
        any(p.startswith(path + '/') for path in BENCHMARK['paths'])
        and os.path.isfile(os.path.join(ROOT, p)) for p in program)


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips():
    four = [w for w in CELLS.values() if w['chips'] == 4]
    assert all(w['chips'] in (1, 4) for w in CELLS.values())
    assert len(four) <= max(1, len(CELLS) // 4)


def test_cell_pairs_and_names_are_unique():
    pairs = [(w['config'], w['traffic']) for w in BENCHMARK['workloads']]
    assert len(set(pairs)) == len(pairs)
    metric_names = [m['name'] for m in BENCHMARK['end_to_end']
                    + BENCHMARK['per_layer']]
    assert len(set(metric_names)) == len(metric_names)
    assert 'setup_s' in END_TO_END and END_TO_END['setup_s']['bound'] <= 0.1


@pytest.mark.parametrize('name', names('configs', '.json'))
def test_configuration_file(name):
    config = load('configs', name)
    entry = CONFIGS[name]                      # every file is listed
    assert config['name'] == name and NAME.match(name)
    assert entry['file'] == 'benchmark/configs/%s.json' % name
    assert sorted(entry['reduced']) == sorted(config['reduced'])
    assert set(entry) == {'name', 'source', 'file', 'reduced', 'why'}
    assert one_line(entry['source']) and one_line(entry['why'])
    assert any(w['config'] == name for w in CELLS.values())
    family = importlib.import_module('benchmark.models.'
                                     + config['family'])
    for attr in ('build', 'reference_loss_and_grad_norm',
                 'to_reference_params', 'flops_per_token'):
        assert callable(getattr(family, attr))
    widths = {'hidden_size', 'intermediate_size', 'num_attention_heads'}
    assert not widths & set(config['reduced'])
    assert isinstance(config['assumed'], list)


@pytest.mark.parametrize('name', names('workloads', '.json'))
def test_cell_file(name):
    cell = load('workloads', name)
    entry = CELLS[name]                        # every file is listed
    assert NAME.match(name) and cell['name'] == name
    assert set(entry) == {'name', 'config', 'traffic', 'chips', 'why'}
    for key in ('config', 'traffic', 'chips', 'why'):
        assert cell[key] == entry[key], key
    assert NAME.match(cell['traffic']) and one_line(cell['why'])
    config = load('configs', cell['config'])
    traffic = load('traffic', cell['traffic'])
    importlib.import_module('benchmark.engines.' + cell['engine'])
    generator = importlib.import_module('benchmark.generators.'
                                        + traffic['generator'])
    assert callable(generator.batches)
    assert traffic['seq'] <= config['max_position_embeddings']
    assert traffic['global_batch'] % cell['chips'] == 0
    assert cell['trace_steps'] >= 1
    assert set(cell['expects']) == {'pallas_custom_calls', 'collectives'}
    # the parallel layout uses exactly the chips the cell asks for
    degrees = [v for k, v in cell['parallel'].items()
               if k in ('dp', 'tp', 'pp', 'sp', 'ep')]
    product = 1
    for d in degrees:
        product *= d
    assert product == cell['chips']


CELL_KEYS = {'name', 'config', 'traffic', 'chips', 'engine', 'parallel',
             'trace_steps', 'expects', 'why'}


@pytest.mark.parametrize('name', names('workloads', '.json'))
def test_cell_file_keys_and_its_windows_least_steps(name):
    """What ``harness.run_cell`` reads of a cell's file, and no other key.
    ``min_steps`` (PR 50) is the cell's to leave out: a run then has
    ``harness.MIN_STEPS``; where it is there it asks for MORE, and leaves
    steps before the traced ones."""
    from benchmark import harness
    cell = load('workloads', name)
    assert CELL_KEYS <= set(cell) <= CELL_KEYS | {'min_steps'}
    least = cell.get('min_steps', harness.MIN_STEPS)
    assert isinstance(least, int) and least >= harness.MIN_STEPS
    if 'min_steps' in cell:
        assert least > harness.MIN_STEPS and least > cell['trace_steps']
    # the loss's fall is read between the first five steps and the last five
    assert max(least, cell['trace_steps'] + 1) >= 10


def test_the_cell_with_streams_says_its_windows_steps():
    # what its 20 s window has already (PERF.md section 6, PR 50)
    cell = load('workloads', 'xing4.0-29b-a4b.s4096.c1')
    assert (cell['min_steps'], cell['trace_steps']) == (45, 6)


def test_both_four_chip_cells_report_the_collectives():
    four = sorted(w['name'] for w in CELLS.values() if w['chips'] == 4)
    for name in ('collective_ms_per_step', 'collective_exposed_pct',
                 'collective_bytes_per_step'):
        assert sorted(PER_LAYER[name]['workloads']) == four
        for cell in four:
            assert 'all-reduce' in load('workloads',
                                        cell)['expects']['collectives']


@pytest.mark.parametrize('name', names('layer_metrics', '.py'))
def test_layer_metric_file(name):
    module = importlib.import_module('benchmark.layer_metrics.' + name)
    entry = PER_LAYER[name]                    # every file is listed
    assert NAME.match(name) and callable(module.reduce)
    assert set(entry) - {'workloads'} == {'name', 'unit', 'better',
                                          'source', 'layer', 'moves'}
    assert (entry['unit'], entry['better'], entry['source'],
            entry['layer'], entry['moves']) == (
        module.UNIT, module.BETTER, module.SOURCE, module.LAYER,
        module.MOVES)
    assert UNIT.match(module.UNIT) and module.BETTER in ('lower', 'higher')
    assert module.SOURCE in SOURCES and one_line(module.LAYER)
    assert module.MOVES in END_TO_END
    for cell in entry.get('workloads', []):
        assert cell in CELLS
    if name.endswith('_roofline_pct'):
        assert module.UNIT == '%'
    # the layer is one of PERF.md's list of layers, letter for letter
    with open(os.path.join(ROOT, 'PERF.md')) as f:
        assert '| %s |' % module.LAYER in f.read()


@pytest.mark.parametrize('name', sorted(END_TO_END))
def test_end_to_end_entry(name):
    entry = END_TO_END[name]
    assert set(entry) - {'workloads'} == {'name', 'unit', 'better',
                                          'bound', 'source'}
    assert NAME.match(name) and UNIT.match(entry['unit'])
    assert entry['source'] in ('host_clock', 'device_trace')
    assert 0.01 <= entry['bound'] <= 0.1


def test_every_listed_metric_has_a_reader():
    assert sorted(PER_LAYER) == names('layer_metrics', '.py')


def test_peaks_table_names_its_source():
    with open(os.path.join(BENCH, 'peaks.json')) as f:
        table = json.load(f)
    row = table['TPU v5 lite']
    assert row['bf16_flops_per_s'] == 197e12
    assert row['hbm_bytes_per_s'] == 819e9 and row['source']
    from benchmark import harness
    with pytest.raises(RuntimeError, match='no default'):
        harness.load_peaks('TPU v9 imaginary')
