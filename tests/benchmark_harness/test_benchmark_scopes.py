"""``scope_reduce`` and ``span_reduce`` (PR 24): device time by the names
the program gives its step, and the device's idle time by the program's
loop spans. The classifier is pinned on lines of the real TPU step; the
sums on a trace built by hand, whose numbers are worked out in the
comments."""
import importlib
import json
import os

import pytest

from bench_paths import BENCH, tiny_config

from benchmark import harness, scope_reduce, span_reduce
from benchmark import trace_reduce as tr
from benchmark.trim_trace import text_proto

# -- the classifier, on lines of the real step -----------------------------
#
# Copied from the text of bert-large.s512.c1's step compiled for the v5e
# (operand lists and backend_config cut): the ``op_name`` forms that JAX
# 0.9.0 gives scopes under value_and_grad, the layer scan and per-block
# jax.checkpoint. The last four are the parent's kernel calls as PR 22's
# chip run recorded them (benchmark/testdata/bert-large.s512.c1.step.json).
STEP = 'jit(step_fn)/'
SCAN = 'while/body/closed_call/'
REAL = {
    'forward mlp fusion': (
        '%fusion.297 = bf16[96,512,1024]{2,1,0:T(8,128)(2,1)} fusion(%param_1.1240), '
        'kind=kLoop, calls=%fused_computation.139.clone, metadata={op_name="' + STEP +
        'jvp()/' + SCAN + 'block/mlp/convert_element_type" stack_frame_id=105}',
        ('forward', 'mlp', None)),
    'forward embedding, outermost scope inside jvp()': (
        '%fusion.197.clone.1 = bf16[30522,1024,1]{1,0,2:T(8,128)(2,1)} fusion(%param_4.512), '
        'kind=kLoop, calls=%fused_computation.252.clone.clone, metadata={op_name="' + STEP +
        'jvp(embed)/convert_element_type" stack_frame_id=36}',
        ('forward', 'embed', None)),
    'recomputed mlp fusion': (
        '%fusion.307 = bf16[96,512,1024]{2,1,0:T(8,128)(2,1)} fusion(%param_1.1258), '
        'kind=kLoop, calls=%fused_computation.51.clone.1, metadata={op_name="' + STEP +
        'transpose(jvp())/' + SCAN + 'checkpoint/rematted_computation/block/mlp/'
        'convert_element_type" stack_frame_id=92}',
        ('recompute', 'mlp', None)),
    'backward weight gradient of attention': (
        '%bitcast_dynamic-update-slice_fusion.11 = f32[24,1024,1024]{2,1,0:T(8,128)} '
        'fusion(%get-tuple-element.982, %subtract.6), kind=kOutput, '
        'calls=%fused_computation.89.clone.clone, metadata={op_name="' + STEP +
        'transpose(jvp())/' + SCAN + 'checkpoint/block/attention/dot_general" '
        'stack_frame_id=92}, backend_config={}',
        ('backward', 'attention', None)),
    'backward head, two names joined': (
        '%select.12 = f32[96,512,30522]{1,2,0:T(8,128)} select(%eq.29, %broadcast.401), '
        'metadata={op_name="' + STEP + 'transpose(jvp(head_loss))/mul;' + STEP +
        'transpose(jvp(head_loss))/broadcast_in_dim"}',
        ('backward', 'head_loss', None)),
    'optimizer': (
        '%fusion.221 = (f32[24,1024]{1,0:T(8,128)}, f32[24,1024]{1,0:T(8,128)}) '
        'fusion(%state_params__blocks____ln1____bias__.1, %sub.99), kind=kLoop, '
        'calls=%fused_computation.298, metadata={op_name="' + STEP + 'optimizer/add" '
        'stack_frame_id=153}, backend_config={}',
        ('optimizer', None, None)),
    'flash_fwd, first forward': (
        '%flash_fwd.14 = (bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)}, f32[96,16,512,1]'
        '{3,2,1,0:T(8,128)}) custom-call(%get-tuple-element.867), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, '
        'metadata={op_name="' + STEP + 'jvp()/' + SCAN +
        'block/attention/flash_fwd/pallas_call" stack_frame_id=87}',
        ('forward', 'attention', 'flash_fwd')),
    'flash_fwd again under remat': (
        '%flash_fwd.15 = (bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)}, f32[96,16,512,1]'
        '{3,2,1,0:T(8,128)}) custom-call(%bitcast.291), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, '
        'metadata={op_name="' + STEP + 'transpose(jvp())/' + SCAN +
        'checkpoint/rematted_computation/block/attention/flash_fwd/pallas_call" '
        'stack_frame_id=92}',
        ('recompute', 'attention', 'flash_fwd')),
    'flash_dq': (
        '%flash_dq.10 = bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)} custom-call(%bitcast.291, '
        '%pallas_call.55, /*index=5*/%copy.116), custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={}}, metadata={op_name="' + STEP +
        'transpose(jvp())/' + SCAN + 'checkpoint/block/attention/flash_dq/pallas_call" '
        'stack_frame_id=92}',
        ('backward', 'attention', 'flash_dq')),
    'flash_dkv, the ROOT of its computation': (
        'ROOT %flash_dkv.10 = (bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)}, bf16[96,16,512,64]'
        '{3,2,1,0:T(8,128)(2,1)}) custom-call(%bitcast.291), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, '
        'metadata={op_name="' + STEP + 'transpose(jvp())/' + SCAN +
        'checkpoint/block/attention/flash_dkv/pallas_call" stack_frame_id=92}',
        ('backward', 'attention', 'flash_dkv')),
    'flash_fwd inside the dp=4 shard_map': (
        '%flash_fwd.3 = (bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)}) custom-call(%bitcast.1), '
        'custom_call_target="tpu_custom_call", metadata={op_name="' + STEP +
        'transpose(jvp())/' + SCAN + 'checkpoint/rematted_computation/block/attention/'
        'shard_map/flash_fwd/pallas_call"}',
        ('recompute', 'attention', 'flash_fwd')),
    'a copy XLA inserted has no name': (
        '%copy.111 = bf16[96,512,1,16,64]{4,1,3,0,2:T(8,128)(2,1)} '
        'copy(%get-tuple-element.894), backend_config={}',
        (None, None, None)),
    'a name that lost its path': (
        '%reduce_sum.57 = f32[]{:T(128)} parameter(1), metadata={op_name="reduce_sum"}',
        (None, None, None)),
    'the primitive transpose in the forward pass is not the backward pass': (
        '%transpose.5 = f32[8,4]{1,0} transpose(%p), metadata={op_name="jit(f)/jvp(block)/'
        'mlp/transpose"}',
        ('forward', 'mlp', None)),
    'parent: forward kernel': (
        '%closed_call.8 = (bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)}) custom-call(%q), '
        'custom_call_target="tpu_custom_call", metadata={op_name="' + STEP + 'jvp()/' +
        SCAN + 'pallas_call" stack_frame_id=106}',
        ('forward', None, None)),
    'parent: forward kernel again under remat': (
        '%rematted_computation.10 = (bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)}) '
        'custom-call(%bitcast.291), custom_call_target="tpu_custom_call", '
        'metadata={op_name="' + STEP + 'transpose(jvp())/' + SCAN +
        'checkpoint/rematted_computation/pallas_call" stack_frame_id=165}',
        ('recompute', None, None)),
    'parent: dq and dkv read alike': (
        '%checkpoint.20 = bf16[96,16,512,64]{3,2,1,0:T(8,128)(2,1)} custom-call(%bitcast.291), '
        'custom_call_target="tpu_custom_call", metadata={op_name="' + STEP +
        'transpose(jvp())/' + SCAN + 'checkpoint/pallas_call" stack_frame_id=165}',
        ('backward', None, None)),
    'parent: the optimizer has no scope': (
        '%fusion.221 = f32[24,1024]{1,0:T(8,128)} fusion(%sub.99), kind=kLoop, '
        'calls=%fused_computation.298, metadata={op_name="' + STEP + 'add"}',
        (None, None, None)),
}


@pytest.mark.parametrize('case', sorted(REAL))
def test_classifier_on_lines_of_the_real_step(case):
    line, expected = REAL[case]
    classes = scope_reduce.op_classes('  ' + line)
    head = line.replace('ROOT ', '').split(' = ')[0]
    assert classes == {head: expected}


def test_op_classes_skips_what_is_not_an_instruction():
    hlo = '\n'.join(['HloModule jit_step_fn, entry_computation_layout={()}',
                     '%fused_computation.1 (p: f32[8]) -> f32[8] {',
                     '  ' + REAL['optimizer'][0], '}',
                     '1 {file_name_id=1 function_name_id=1 line=42}'])
    assert list(scope_reduce.op_classes(hlo)) == ['%fusion.221']
    assert scope_reduce.has_program_scopes(scope_reduce.op_classes(hlo))
    parent = scope_reduce.op_classes('\n'.join(
        REAL[k][0] for k in REAL if k.startswith('parent')))
    assert len(parent) == 4 and not scope_reduce.has_program_scopes(parent)


# -- a trace built by hand -------------------------------------------------

def line(head, op, op_name, kernel=False):
    return '  %s = bf16[1,1,128,64]{3,2,1,0} %s(%%p)%s%s' % (
        head, op, ', custom_call_target="tpu_custom_call"' if kernel else '',
        ', metadata={op_name="%s"}' % op_name if op_name else '')


BWD = STEP + 'transpose(jvp())/' + SCAN + 'checkpoint/'
OPS = {   # head: (operation, op_name, is a kernel)
    '%fusion.1': ('fusion', STEP + 'jvp(embed)/convert_element_type', False),
    '%fusion.2': ('fusion', STEP + 'jvp()/' + SCAN + 'block/mlp/dot_general', False),
    '%flash_fwd.14': ('custom-call', STEP + 'jvp()/' + SCAN +
                      'block/attention/flash_fwd/pallas_call', True),
    '%copy.111': ('copy', None, False),
    '%fusion.4': ('fusion', STEP + 'transpose(jvp(head_loss))/mul', False),
    '%flash_fwd.15': ('custom-call', BWD + 'rematted_computation/block/attention/'
                      'flash_fwd/pallas_call', True),
    '%fusion.3': ('fusion', BWD + 'rematted_computation/block/mlp/add', False),
    '%flash_dq.10': ('custom-call', BWD + 'block/attention/flash_dq/pallas_call', True),
    '%flash_dkv.10': ('custom-call', BWD + 'block/attention/flash_dkv/pallas_call', True),
    '%fusion.5': ('fusion', STEP + 'optimizer/add', False),
    '%while.6': ('while', STEP + 'jvp()/while', False),
    '%while.7': ('while', STEP + 'transpose(jvp())/while', False),
}
HLO = '\n'.join(line(h, *v) for h, v in OPS.items())
EV = {h: line(h, *v).strip() for h, v in OPS.items()}

# Two steps, the window is 1000..11000 ns. Chip 0 runs, in each step, the
# embedding (forward, 100 ns), the forward scan (a container) around an
# mlp fusion (300), flash_fwd (400) and an unnamed copy (100); after a
# pause of 300 ns the head's backward (200) and the backward scan around
# flash_fwd again (recompute, 400), a recomputed mlp fusion (200),
# flash_dq (300) and flash_dkv (500); then the optimizer (300): busy
# 1600-2500 and 2800-4700, and 5000 ns later the same. Chip 1 runs one
# optimizer fusion of 2000 ns.
#
# Phases, ns in the window (chip 0; chip 1; mean; a step):
#   forward      2 x (100 + 300 + 400) = 1600;    0;  800; 400
#   recompute    2 x (400 + 200)       = 1200;    0;  600; 300
#   backward     2 x (200 + 300 + 500) = 2000;    0; 1000; 500
#   optimizer    2 x 300               =  600; 2000; 1300; 650
#   unattributed 2 x 100               =  200;    0;  100;  50 = 2.6316% of
#   busy         2 x 2800              = 5600; 2000; 3800; 1900 = device_step_ms
# Components (mean a step): attention (400 + 400 + 300 + 500) x 2 / 2 / 2 = 800,
# mlp 250, head_loss 100. Kernels: flash_fwd 400, flash_dq 150, flash_dkv
# 250 a step, together 800 = flash_ms_per_step.
# Rooflines, calls of [1, 1, 128, 64] bf16 counted on chip 0, one score
# matmul 2 x 128 x 128 x 64 = 2,097,152 FLOPs, one tensor 16,384 bytes,
# at 1e14 FLOP/s and 1e12 B/s:
#   flash_fwd 2 calls: 2 x 2 matmuls = 83.89 ns, 2 x 4 tensors = 131.072 ns
#     (memory-bound) of 400: 32.768%
#   flash_dq  1 call: 3 matmuls = 62.91 ns, 5 tensors = 81.92 ns of 150: 54.6133%
#   flash_dkv 1 call: 4 matmuls = 83.89 ns, 6 tensors = 98.304 ns of 250: 39.3216%


def step_events(t):
    return [(EV['%fusion.1'], t + 600, 100), (EV['%while.6'], t + 700, 800),
            (EV['%fusion.2'], t + 700, 300), (EV['%flash_fwd.14'], t + 1000, 400),
            (EV['%copy.111'], t + 1400, 100),
            (EV['%fusion.4'], t + 1800, 200), (EV['%while.7'], t + 2000, 1400),
            (EV['%flash_fwd.15'], t + 2000, 400), (EV['%fusion.3'], t + 2400, 200),
            (EV['%flash_dq.10'], t + 2600, 300), (EV['%flash_dkv.10'], t + 2900, 500),
            (EV['%fusion.5'], t + 3400, 300)]


HAND = {
    '/device:TPU:0': {'XLA Ops': step_events(1000) + step_events(6000)},
    '/device:TPU:1': {'XLA Ops': [(EV['%fusion.5'], 2000, 2000)]},
    '/host:CPU': {'python3': [('fit.step', 1000, 5000), ('data.next', 1000, 200),
                              ('fit.step', 6000, 5000), ('data.next', 6000, 200),
                              ('trainer.step', 1250, 450)]},
}

# The host's clock reads T0 seconds where the trace's host plane reads 0
# ns. The loop, in trace ns: input 900-1200, step 1250-1700, read-back
# 1750-5100, input 5150-6100, step 6150-6700, read-back 6750-10150, input
# 10200-11500. The device planes run 100 ns early: chip 0 restarts at 1600
# and 6600 where its trainer.step spans end at 1700 and 6700 (chip 1's
# one restart at 2000 gives -300: the median of 100, 100, -300 is 100;
# the pauses of 300 ns are under a quarter of the median long gap, 1600
# and 4000 ns, and no restart). Shifted by +100, chip 0 is idle 1000-1700,
# 2600-2900, 4800-6700, 7600-7900 and 9800-11000 (4400 ns, as unshifted):
#   input     1000-1200, 5150-6100, 10200-11000        = 1950
#   dispatch  1250-1700, 6150-6700                     = 1000
#   read-back ends at 5100 in the gap from 4800, at 10150 in the gap from
#             9800: 300 + 350                          =  650
#   the rest  the two pauses of 300 inside the steps (the read-back spans
#             cover them, but they are not its tail) and four times 50
#             between two spans                        =  800
# Chip 1 is idle 1000-2100 and 4100-11000 (8000 ns): input 1950, dispatch
# 1000, read-back (5100 - 4100) + (10150 - 6750) = 4400, the rest 650.
# Mean over the chips, a step: input 975, dispatch 500, read-back 1262.5,
# the rest 362.5; together 3100 = host_gap_ms.
T0 = 100.0
LOOP = [('trainer.fit', 800, 11000), ('trainer.source', 950, 100),
        ('trainer.place', 1060, 100), ('trainer.input', 900, 300),
        ('trainer.new_step_signature', 1260, None),
        ('trainer.step', 1250, 450), ('trainer.loss_readback', 1750, 3350),
        ('trainer.input', 5150, 950), ('trainer.step', 6150, 550),
        ('trainer.loss_readback', 6750, 3400), ('trainer.input', 10200, 1300),
        ('trainer.step', 11600, 300)]        # past the window
RECORDS = [{'name': name, 't0': T0 + start * 1e-9,
            'dur': None if dur is None else dur * 1e-9, 'step': 1}
           for name, start, dur in LOOP]
RUN = {
    'config': {'num_hidden_layers': 1, 'num_attention_heads': 1,
               'hidden_size': 64, 'causal': False},
    'traffic': {'global_batch': 2, 'seq': 128},
    'chips': 2,
    'peaks': {'bf16_flops_per_s': 1e14, 'hbm_bytes_per_s': 1e12},
    'hlo': HLO,
    'step_times': [T0 + 1000e-9, T0 + 6000e-9, T0 + 11000e-9],
}
HAND_VALUES = {
    'forward_ms_per_step': 400e-6,
    'recompute_ms_per_step': 300e-6,
    'backward_ms_per_step': 500e-6,
    'optimizer_ms_per_step': 650e-6,
    'unattributed_device_pct': 100 * 100 / 3800,
    'attention_ms_per_step': 800e-6,
    'mlp_ms_per_step': 250e-6,
    'head_loss_ms_per_step': 100e-6,
    'flash_fwd_roofline_pct': 32.768,
    'flash_dq_roofline_pct': 100 * 81.92 / 150,
    'flash_dkv_roofline_pct': 39.3216,
    'host_gap_input_ms': 975e-6,
    'host_gap_dispatch_ms': 500e-6,
    'host_gap_readback_ms': 1262.5e-6,
    'host_gap_unattributed_ms': 362.5e-6,
}


@pytest.fixture(scope='module')
def hand():
    from jax.profiler import ProfileData
    return tr.load(ProfileData.from_text_proto(text_proto(HAND)))


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: RECORDS)


def metric(name, trace, run, said=None):
    """One reader's value. A ``run`` that has its ``say`` is handed on as
    it is, as the harness hands ONE run to every reader (what a reader
    keeps on it, the next one finds); else a run of its own."""
    module = importlib.import_module('benchmark.layer_metrics.' + name)
    if 'say' not in run:
        say = (lambda line: None) if said is None else said.append
        run = dict(run, say=say)
    return module.reduce(trace, run)


@pytest.mark.parametrize('name', sorted(HAND_VALUES))
def test_new_layer_metric_on_the_hand_built_trace(hand, ring, name):
    # 1e-6: the host readings are 100 s as doubles, good to 1e-5 ns
    assert metric(name, hand, RUN) == pytest.approx(HAND_VALUES[name],
                                                    rel=1e-6)


def test_the_parts_add_up_to_the_whole(hand, ring):
    said = []
    run = dict(RUN, say=said.append)       # one run, as the harness's
    value = {n: metric(n, hand, run) for n in list(HAND_VALUES) + [
        'device_step_ms', 'flash_ms_per_step', 'host_gap_ms']}
    busy = value['device_step_ms']
    assert busy == pytest.approx(1900e-6)
    assert sum(value[p + '_ms_per_step'] for p in scope_reduce.PHASES) \
        + value['unattributed_device_pct'] / 100 * busy == pytest.approx(busy)
    assert value['host_gap_ms'] == pytest.approx(3100e-6)
    assert sum(value['host_gap_%s_ms' % p] for p in span_reduce.PARTS) \
        == pytest.approx(value['host_gap_ms'], rel=1e-6)
    # each kernel's reader says its milliseconds a step
    kernel_ms = {line.split(':')[0]: float(line.split()[1]) for line in said
                 if line.split(':')[0] in scope_reduce.KERNELS}
    assert kernel_ms == pytest.approx({'flash_fwd': 400e-6, 'flash_dq': 150e-6,
                                       'flash_dkv': 250e-6})
    assert sum(kernel_ms.values()) == pytest.approx(value['flash_ms_per_step'])
    # the split is made once for the run and kept on it: said once
    assert [line for line in said if 'host gap split' in line][1:] == [
        'host gap split: device planes shifted by +100 ns, to start each '
        'step where its trainer.step span ends']
    assert set(run['host_gap_split']) == set(span_reduce.PARTS)
    assert any(line.startswith('flash_fwd: ') and '2 calls' in line
               and 'bound by memory' in line for line in said)


def test_a_step_without_the_programs_names_reads_as_nothing_not_as_zero(hand):
    # the same trace against the text of an executable from a cache an
    # older program filled: JAX's wrappers are there, the scopes are not
    stale = HLO
    for scope in ('block/attention/', 'block/mlp/', 'optimizer/', 'flash_fwd/',
                  'flash_dq/', 'flash_dkv/', 'embed', 'head_loss'):
        stale = stale.replace(scope, '')
    assert not scope_reduce.has_program_scopes(scope_reduce.op_classes(stale))
    said = []
    value = {n: metric(n, hand, dict(RUN, hlo=stale), said) for n in HAND_VALUES
             if not n.startswith('host_gap')}
    for name in ('forward', 'recompute', 'backward'):   # JAX's own wrappers
        assert value[name + '_ms_per_step'] == pytest.approx(
            HAND_VALUES[name + '_ms_per_step'])
    for name in ('optimizer_ms_per_step', 'attention_ms_per_step',
                 'mlp_ms_per_step', 'head_loss_ms_per_step',
                 'flash_fwd_roofline_pct', 'flash_dq_roofline_pct',
                 'flash_dkv_roofline_pct'):
        assert value[name] is None
        assert any(line.startswith(name) and 'names' in line for line in said)
    # the optimizer's 650 ns a step are now in it: (50 + 650) / 1900
    assert value['unattributed_device_pct'] == pytest.approx(100 * 700 / 1900)
    assert any('optimizer\'s operations count as unattributed' in line
               for line in said)


def test_a_program_without_the_ring_reads_as_nothing(hand, monkeypatch):
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: None)
    said = []
    run = dict(RUN, say=said.append)
    assert [metric('host_gap_%s_ms' % p, hand, run)
            for p in span_reduce.PARTS] == [None] * 4
    assert said == ['host gap split: the program has no loop ring '
                    '(autodist_tpu.telemetry.get().loop_records)']
    # records, but none of the loop's inside the window
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: RECORDS[-1:])
    assert metric('host_gap_input_ms', hand, RUN, said) is None
    assert 'none of 1 loop records' in said[-1]


def test_the_two_clocks_are_tied_by_the_median_over_the_traced_steps(hand):
    # the readings lag the spans' starts by 0, 40 and 10 ns: median 10
    times = [T0 + 1000e-9, T0 + 6040e-9, T0 + 11010e-9, T0 + 16000e-9]
    from jax.profiler import ProfileData
    three = tr.load(ProfileData.from_text_proto(text_proto({'/host:CPU': {
        'python3': [('fit.step', 1000, 5000), ('fit.step', 6000, 5000),
                    ('fit.step', 11000, 5000)]}})))
    offset = span_reduce.clock_offset_ns(three, times)
    assert offset == pytest.approx(-1e9 * T0 - 10, abs=1e-3)
    spans = span_reduce.spans_on_trace(
        [{'name': 'trainer.step', 't0': T0 + 2010e-9, 'dur': 500e-9},
         {'name': 'trainer.step', 't0': T0 + 900e-9, 'dur': 50e-9},    # before
         {'name': 'trainer.new_step_signature', 't0': T0 + 2e-6, 'dur': None}],
        offset, three.window)
    assert list(spans) == ['trainer.step']
    assert spans['trainer.step'][0] == pytest.approx((2000, 2500), abs=1e-3)
    # fewer readings than spans: nothing ties the clocks
    assert span_reduce.clock_offset_ns(three, times[:2]) is None
    assert span_reduce.intersect([(0, 10), (20, 30)], [(5, 25)]) == [
        (5, 10), (20, 25)]


# -- the trace recorded on the chip ----------------------------------------

def test_recorded_kernels_split_into_forward_recompute_and_backward():
    # PR 22's recording of the parent's step: the rule has to tell the
    # second forward call from dq and dkv by `rematted_computation` alone
    base = os.path.join(BENCH, 'testdata', 'bert-large.s512.c1.step')
    with open(base + '.json') as f:
        facts = json.load(f)
    trace = tr.load_file(base + '.textproto.gz')
    classes = scope_reduce.op_classes('\n'.join(facts['run']['hlo_lines']))
    assert {h: c[0] for h, c in classes.items()} == {
        '%closed_call.8': 'forward', '%rematted_computation.10': 'recompute',
        '%checkpoint.20': 'backward', '%checkpoint.21': 'backward'}
    split = scope_reduce.split_ns(trace, classes, lambda c: c[0])
    kernels = facts['by_brute_force']['kernel_ns']['0']
    assert split['forward'] + split['recompute'] + split['backward'] \
        == pytest.approx(kernels, abs=50)
    # 24 calls each of the same kernel on the same shapes
    assert split['recompute'] == pytest.approx(split['forward'], rel=0.01)
    assert split['backward'] > 1.9 * split['forward']
    assert sum(split.values()) == pytest.approx(
        facts['by_brute_force']['busy_ns']['0'], abs=50)


# -- the run loop ----------------------------------------------------------

def test_rehearsal_ties_the_programs_ring_to_the_traces_clock(tmp_path):
    cell = dict(name='bert-large.s512.c1', config='tiny', traffic='tiny',
                chips=1, engine='trainer', parallel={'dp': 1}, trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    traffic = dict(generator='zipf_lm', seq=32, global_batch=4,
                   zipf_exponent=1.1)
    result, lines = harness.rehearse(
        cell, tiny_config(True), traffic,
        {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}, seed=2 ** 31 + 7,
        trace=True, out_dir=str(tmp_path))
    assert result['correct'] is True
    # the CPU has no device plane, so there is no idle gap to split and
    # the four readers return nothing (test_benchmark_run.py holds the
    # traced rehearsal to the four metrics a CPU run can give); what the
    # rehearsal shows is the program's ring on the trace's clock
    assert not any(name.startswith('host_gap') for name in result['metrics'])
    said = [line for line in lines if line.startswith('host gap split')]
    assert len(said) == 1
    for part in ('3 trainer.input', '3 trainer.step',
                 '3 trainer.loss_readback', '1 trainer.fit'):
        assert part in said[0]
