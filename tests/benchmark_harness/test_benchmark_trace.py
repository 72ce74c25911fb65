"""``trace_reduce`` and every trace-reading layer metric on two traces:
one built by hand, whose numbers are worked out in the comments, and
one recorded on the chip (``benchmark/testdata``), whose numbers were
worked out from its event list when it was recorded (PR 22)."""
import importlib
import json
import os
import statistics

import numpy as np
import pytest

from bench_paths import BENCH

from benchmark import span_reduce
from benchmark import trace_reduce as tr
from benchmark.trim_trace import text_proto


F1 = '%fusion.1 = bf16[8,128]{1,0} fusion(%p0, %all-reduce.3)'  # operand text
F2 = '%fusion.2 = bf16[8,128]{1,0} fusion(%p1)'
F9 = '%fusion.9 = f32[8]{0} fusion(%p2)'
WHILE = '%while.1 = (s32[], bf16[8,128]{1,0}) while(%tuple.1)'
FWD = '%closed_call.8 = (bf16[1,1,128,64]{3,2,1,0}) custom-call(%q, %k, %v)'
DQ = '%checkpoint.20 = bf16[1,1,128,64]{3,2,1,0} custom-call(%q, %k, %v)'
DKV = '%checkpoint.21 = (bf16[1,1,128,64]{3,2,1,0}) custom-call(%q, %k)'
AR = '%all-reduce.3 = f32[8]{0} all-reduce(%fusion.2)'
HLO = '\n'.join([
    '  %s, custom_call_target="tpu_custom_call"' % FWD,
    '  ROOT %s, custom_call_target="tpu_custom_call"' % DQ,
    '  %s, custom_call_target="tpu_custom_call"' % DKV,
    '  %custom-call.5 = f32[8]{0} custom-call(%x), custom_call_target="Sharding"',
    '  %s, replica_groups={{0,1}}' % AR,
])

# Two steps, the window is 1000..11000 ns (10000 ns). Chip 0 runs, in
# step 1, a while (a container, never counted) around fusion.1, the three
# kernels and fusion.2, then an all-reduce that nothing overlaps; in step
# 2 fusion.1, the kernels, an all-reduce that the last kernel overlaps by
# 100 ns, and a fusion that runs 500 ns past the window. Chip 1 runs one
# fusion of 5000 ns.
HAND = {
    '/device:TPU:0': {
        'XLA Ops': [
            (WHILE, 1500, 4000), (F1, 1600, 1000), (FWD, 2600, 400),
            (DQ, 3000, 300), (DKV, 3300, 300), (F2, 3700, 1700),
            (AR, 5500, 600),
            (F1, 6500, 1000), (FWD, 7500, 400), (DQ, 7900, 300),
            (DKV, 8200, 300), (AR, 8400, 600), (F9, 10800, 700)],
        'XLA Modules': [('jit_step_fn(123)', 1500, 4600)],
    },
    '/device:TPU:1': {'XLA Ops': [(F1, 2000, 5000)]},
    '/host:CPU': {
        'python3': [('fit.step', 1000, 5000), ('data.next', 1000, 200),
                    ('fit.step', 6000, 5000), ('data.next', 6000, 450),
                    ('$other.py:1 f', 0, 12000)],
        'worker': [('something else', 0, 100)],
    },
}
# Chip 0 is busy 1600-3600, 3700-5400, 5500-6100, 6500-9000 and
# 10800-11000: 2000 + 1700 + 600 + 2500 + 200 = 7000 ns; chip 1 5000 ns.
# Mean busy 6000 ns of 10000: idle 40%; per step 3000 ns busy, 2000 idle.
# Kernels: chip 0 2 x 1000 ns, chip 1 none: mean 1000 ns, 500 ns a step.
# Collectives: chip 0 runs 1200 ns, 600 + 500 of it alone; chip 1 none:
# mean 600 ns (300 a step), 550 ns alone = 5.5% of the window; its two
# all-reduces of f32[8] are 64 bytes, 16 bytes a step and chip.
# Roofline: three kernel calls a step and one layer is one forward call
# and one backward pair of [1, 1, 128, 64] bf16: one score matmul is
# 2 x 128 x 128 x 64 = 2,097,152 FLOPs and one tensor 16,384 bytes, so
# (2 + 5) x 2,097,152 = 14,680,064 FLOPs and (4 + 8) x 16,384 = 196,608
# bytes; at 1e14 FLOP/s and 1e12 B/s that is 146.8 ns against 196.6 ns:
# memory-bound, 196.608 / 500 = 39.3216%.
RUN = {
    'config': {'num_hidden_layers': 1, 'num_attention_heads': 1,
               'hidden_size': 64, 'causal': False},
    'traffic': {'global_batch': 2, 'seq': 128},
    'chips': 2,
    'peaks': {'bf16_flops_per_s': 1e14, 'hbm_bytes_per_s': 1e12},
    'hlo': HLO,
    'memory': {'argument': 4e9, 'output': 4e9, 'temp': 12e9, 'alias': 4e9},
    'memory_stats': [{'peak_bytes_reserved': 8e9},
                     {'peak_bytes_reserved': 9e9}],
    'compile': {'requests': 7, 'seconds': 12.5, 'cache_hits': 5},
    'step_times': [10.0, 10.5, 11.25, 11.75],
}
HAND_VALUES = {
    'device_idle_pct': 40.0,
    'device_step_ms': 3000e-6,
    'host_gap_ms': 2000e-6,
    'flash_ms_per_step': 500e-6,
    'flash_roofline_pct': 39.3216,
    'collective_ms_per_step': 300e-6,
    'collective_exposed_pct': 5.5,
    'collective_bytes_per_step': 16e-6,     # f32[8] twice on chip 0 of 2
    'step_hbm_gb': 13.0,                    # 4 GB arguments + 9 GB reserved
    'compile_s': 12.5,
    'compile_cache_miss': 2,
    'step_wall_ms': 500.0,                  # median of 500, 750, 500
}


@pytest.fixture(scope='module')
def hand():
    from jax.profiler import ProfileData
    return tr.load(ProfileData.from_text_proto(text_proto(HAND)))


def test_loader_keeps_device_ops_and_the_benchmark_spans(hand):
    assert sorted(hand.ops) == [0, 1]
    assert len(hand.ops[0]) == 13 and len(hand.ops[1]) == 1
    assert [s.name for s in hand.spans] == ['fit.step', 'data.next',
                                            'fit.step', 'data.next']
    assert hand.window == (1000, 11000) and hand.steps == 2


def test_names_are_matched_on_the_head_only():
    assert tr.op_head(F1) == '%fusion.1'
    assert not tr.is_collective(F1)             # an operand, not the op
    assert tr.is_collective(AR)
    assert tr.is_collective('%all-reduce-start.7 = f32[8] all-reduce-start(%x)')
    assert tr.is_collective('%all-gather-done.2 = f32[8] all-gather-done(%x)')
    assert tr.is_container(WHILE) and not tr.is_container(F2)
    assert tr.pallas_heads(HLO) == {'%closed_call.8', '%checkpoint.20',
                                    '%checkpoint.21'}


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.union_ns([(1, 3), (2, 4), (10, 11)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def old_subtract(a, b):
    """``trace_reduce.subtract`` as it was until PR 50, which scanned ``b``
    from its start for every interval of ``a``: the oracle of the walk."""
    out = []
    b = tr.merge(b)
    for s, t in tr.merge(a):
        cur = s
        for bs, bt in b:
            if bt <= cur:
                continue
            if bs >= t:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, bt)
            if cur >= t:
                break
        if cur < t:
            out.append((cur, t))
    return out


def old_intersect(a, b):
    return old_subtract(a, old_subtract(a, b))


def old_chip_split(gaps, spans):
    """``span_reduce.chip_split`` as it was until PR 50."""
    readback = 0.0
    for start, end in spans.get(span_reduce.READBACK, []):
        for g_start, g_end in gaps:
            if g_start < end <= g_end:
                readback += end - max(start, g_start)
    return {'input': tr.union_ns(old_intersect(
                gaps, spans.get(span_reduce.INPUT, []))),
            'dispatch': tr.union_ns(old_intersect(
                gaps, spans.get(span_reduce.STEP, []))),
            'readback': readback}


@pytest.mark.parametrize('seed', range(4))
def test_the_interval_walk_gives_what_the_rescan_gave(seed):
    """A thousand random pairs of interval lists a seed, on a coarse grid
    so that intervals touch, nest, repeat and are empty: one walk over the
    two merged lists against the old rescan, interval for interval."""
    rng = np.random.default_rng(seed)
    for _ in range(1000):
        lists = []
        for _ in range(2):
            n = int(rng.integers(0, 40))
            starts = rng.integers(0, 200, n)
            lists.append([(int(s), int(s + d)) for s, d in zip(
                starts, rng.integers(0, 12 if seed % 2 else 60, n))])
        a, b = lists
        assert tr.subtract(a, b) == old_subtract(a, b)
        assert span_reduce.intersect(a, b) == old_intersect(a, b)
        assert tr.union_ns(tr.subtract(a, b)) \
            + tr.union_ns(span_reduce.intersect(a, b)) == tr.union_ns(a)


def test_busy_union_and_idle_gaps(hand):
    assert tr.busy_ns(hand, 0) == 7000 and tr.busy_ns(hand, 1) == 5000
    gaps = tr.idle_gaps(hand, 0)
    assert gaps[:3] == [(9000, 10800), (1000, 1600), (6100, 6500)]
    assert sum(t - s for s, t in gaps) == 3000
    assert tr.collective_split(hand, 0) == (1200, 1100)
    assert tr.collective_split(hand, 1) == (0, 0)


def test_self_time_takes_the_nesting_out(hand):
    selfs = {(e.name, e.start): ns for e, ns in tr.self_times(hand.ops[0])}
    # the while holds 3700 ns of children in its 4000
    assert selfs[(WHILE, 1500)] == 300
    assert selfs[(F1, 1600)] == 1000


def test_breakdown(hand):
    b = tr.breakdown(hand)
    assert b['device_ops'][:3] == [['%fusion.1', 2000e-9],
                                   ['%fusion.2', 1700e-9],
                                   ['%all-reduce.3', 1200e-9]]
    assert '%while.1' not in dict(b['device_ops'])
    assert len(b['device_ops']) == 7
    assert b['idle_gaps'][:3] == [['fit.step', 1800e-9],
                                  ['fit.step', 600e-9],
                                  ['data.next', 400e-9]]


@pytest.mark.parametrize('name', sorted(HAND_VALUES))
def test_layer_metric_on_the_hand_built_trace(hand, name):
    said = []
    module = importlib.import_module('benchmark.layer_metrics.' + name)
    value = module.reduce(hand, dict(RUN, say=said.append))
    assert value == pytest.approx(HAND_VALUES[name], rel=1e-9)
    if name == 'flash_roofline_pct':
        assert 'bound by memory' in said[0]


@pytest.mark.parametrize('name', ['flash_roofline_pct',
                                  'collective_ms_per_step',
                                  'collective_exposed_pct',
                                  'collective_bytes_per_step'])
def test_a_reader_that_finds_nothing_returns_nothing(name):
    from jax.profiler import ProfileData
    quiet = tr.load(ProfileData.from_text_proto(text_proto({
        '/device:TPU:0': {'XLA Ops': [(F1, 1500, 1000)]},
        '/host:CPU': {'python3': [('fit.step', 1000, 5000)]}})))
    module = importlib.import_module('benchmark.layer_metrics.' + name)
    assert module.reduce(quiet, dict(RUN, hlo='', say=print)) is None
    flash = importlib.import_module('benchmark.layer_metrics.flash_ms_per_step')
    assert flash.reduce(quiet, dict(RUN, hlo='')) == 0.0


# -- the trace recorded on the chip ----------------------------------------
#
# benchmark/testdata/<name>.textproto.gz is one traced step of a real run,
# cut down by benchmark/trim_trace.py; <name>.json beside it holds the run's
# shapes, the HLO lines of its kernels and collectives, and the numbers a
# separate brute-force sweep over the event list gave when it was recorded
# (every boundary between two events is visited and the events alive there
# are counted: no merging, no stack), which trace_reduce has to reproduce.

FIXTURES = sorted(f[:-len('.textproto.gz')]
                  for f in os.listdir(os.path.join(BENCH, 'testdata'))
                  if f.endswith('.textproto.gz'))


@pytest.fixture(scope='module', params=FIXTURES)
def recorded(request):
    base = os.path.join(BENCH, 'testdata', request.param)
    with open(base + '.json') as f:
        facts = json.load(f)
    run = dict(facts['run'], hlo='\n'.join(facts['run']['hlo_lines']),
               say=lambda line: None)
    return tr.load_file(base + '.textproto.gz'), run, facts['by_brute_force']


def per_chip(expected, key):
    return {int(chip): value for chip, value in expected[key].items()}


def test_recorded_trace_busy_union_and_idle_share(recorded):
    trace, run, expected = recorded
    assert sorted(trace.ops) == list(range(run['chips']))
    assert {c: len(o) for c, o in trace.ops.items()} == per_chip(
        expected, 'events')
    lo, hi = trace.window
    assert hi - lo == pytest.approx(expected['window_ns'], abs=1)
    assert trace.steps == expected['steps'] == 1
    busy = per_chip(expected, 'busy_ns')
    for chip in trace.ops:
        assert tr.busy_ns(trace, chip) == pytest.approx(busy[chip], abs=50)
    module = importlib.import_module('benchmark.layer_metrics.device_idle_pct')
    idle = 100 * (1 - sum(busy.values()) / len(busy) / expected['window_ns'])
    assert module.reduce(trace, run) == pytest.approx(idle, abs=1e-5)
    assert 0 < idle < 5          # a full step: the chip is nearly always busy
    for name, per_step in (('device_step_ms', sum(busy.values())),
                           ('host_gap_ms', len(busy) * expected['window_ns']
                            - sum(busy.values()))):
        module = importlib.import_module('benchmark.layer_metrics.' + name)
        assert module.reduce(trace, run) == pytest.approx(
            per_step / len(busy) / 1e6, abs=1e-4)


def test_recorded_trace_kernel_sum_and_roofline(recorded):
    trace, run, expected = recorded
    kernel = per_chip(expected, 'kernel_ns')
    module = importlib.import_module('benchmark.layer_metrics.flash_ms_per_step')
    mean_ns = sum(kernel.values()) / len(kernel)
    assert module.reduce(trace, run) == pytest.approx(mean_ns / 1e6, abs=1e-4)
    # 24 layers x (forward, forward again under remat, dq, dkv)
    assert set(per_chip(expected, 'kernel_calls').values()) == {96}
    assert tr.pallas_heads(run['hlo']) and mean_ns > 0
    # by hand: 48 forward calls and 24 backward pairs of [96, 16, 512, 64]
    # are 48 x 103,079,215,104 + 24 x 257,698,037,760 = 1.1132555e13 FLOPs
    # (56.5 ms at 197 TFLOP/s) and 48 x 402,653,184 + 24 x 805,306,368 =
    # 3.8654706e10 bytes (47.2 ms at 819 GB/s): compute-bound
    module = importlib.import_module('benchmark.layer_metrics.flash_roofline_pct')
    said = []
    value = module.reduce(trace, dict(run, say=said.append))
    assert value == pytest.approx(
        100 * (11132555231232 / 197e12) / (mean_ns / 1e9), rel=1e-9)
    assert 'bound by compute' in said[0]


def test_recorded_trace_collective_time_and_its_exposed_part(recorded):
    trace, run, expected = recorded
    ran = per_chip(expected, 'collective_ns')
    alone = per_chip(expected, 'collective_alone_ns')
    for chip in trace.ops:
        got = tr.collective_split(trace, chip)
        assert got[0] == pytest.approx(ran[chip], abs=50)
        assert got[1] == pytest.approx(alone[chip], abs=50)
    names = ('collective_ms_per_step', 'collective_exposed_pct',
             'collective_bytes_per_step')
    values = [importlib.import_module('benchmark.layer_metrics.' + n)
              .reduce(trace, run) for n in names]
    if run['chips'] == 1:
        assert values == [None, None, None]
        return
    n = len(ran)
    assert values[0] == pytest.approx(sum(ran.values()) / n / 1e6, abs=1e-4)
    assert values[1] == pytest.approx(
        100 * sum(alone.values()) / n / expected['window_ns'], abs=1e-5)
    # by hand from the HLO: 24 x (25,176,064 + 16,384) in the backward
    # scan + 125,018,112 + 1,048,576 + 8,196 after it = 730,693,636 bytes
    assert values[2] == pytest.approx(730.693636, rel=1e-9)


def test_recorded_trace_host_gap_parts_as_the_old_walk_read_them(
        recorded, monkeypatch):
    """The four parts of ``host_gap_ms`` over the recorded step's thousands
    of idle gaps, with a loop ring laid on it by hand (an input, a dispatch
    that ends where the device restarts, a read-back that ends in the
    step's last gap, and a second input across the middle of the step,
    over thousands of pauses): the merge walk and the one shared split read
    what the quadratic walk read, to 1e-9 ms, and each reader finds the
    split the first one made."""
    trace, run, _ = recorded
    lo, hi = trace.window
    t0 = 100.0                                   # perf_counter at ``lo``
    firsts, lasts = [], []
    for chip in trace.ops:
        gaps = sorted(tr.idle_gaps(trace, chip)[:2])
        firsts.append(gaps[0][1])
        lasts.append(gaps[1])
    restart = statistics.median(firsts)          # the shift puts it at 2 ms
    shift = lo + 2e6 - restart
    back = max(s for s, _ in lasts) + shift + 0.25 * min(
        e - s for s, e in lasts)

    def record(name, start, end):
        return {'name': name, 't0': t0 + (start - lo) / 1e9,
                'dur': (end - start) / 1e9}
    records = [record(span_reduce.INPUT, lo, lo + 1e6),
               record(span_reduce.STEP, lo + 1e6, lo + 2e6),
               record(span_reduce.READBACK, lo + 2e6, back),
               record(span_reduce.INPUT, lo + 0.2 * (hi - lo),
                      lo + 0.6 * (hi - lo))]
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: records)
    said = []
    run = dict(run, step_times=[t0, t0 + (hi - lo) / 1e9], say=said.append)
    new = {part: importlib.import_module(
        'benchmark.layer_metrics.host_gap_%s_ms' % part).reduce(trace, run)
        for part in span_reduce.PARTS}
    assert len([line for line in said if 'host gap split' in line]) == 2
    assert new == run['host_gap_split']
    monkeypatch.setattr(tr, 'subtract', old_subtract)
    monkeypatch.setattr(span_reduce, 'intersect', old_intersect)
    monkeypatch.setattr(span_reduce, 'chip_split', old_chip_split)
    old = span_reduce.gap_split(trace, dict(run, say=said.append))
    assert new == pytest.approx(old, abs=1e-9)
    assert all(new[part] > 0.01 for part in span_reduce.PARTS)
    whole = importlib.import_module(
        'benchmark.layer_metrics.host_gap_ms').reduce(trace, run)
    assert sum(new.values()) == pytest.approx(whole, abs=1e-9)


def test_recorded_trace_breakdown(recorded):
    trace, run, _ = recorded
    b = tr.breakdown(trace)
    assert len(b['device_ops']) == 10 and len(b['idle_gaps']) <= 5
    assert all(not tr.is_container(head) for head, _ in b['device_ops'])
    seconds = [s for _, s in b['device_ops']]
    assert seconds == sorted(seconds, reverse=True)
    assert {span for span, _ in b['idle_gaps']} <= {'fit.step', 'data.next'}
