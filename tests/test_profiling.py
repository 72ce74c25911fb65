"""Per-op profile aggregation (utils/profiling.py): the analysis layer
over RunOptions/jax.profiler traces that produced the round-3/4
performance diagnoses, shipped as a framework utility."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from autodist_tpu.utils.profiling import format_breakdown, per_op_breakdown


def test_breakdown_from_real_trace(tmp_path):
    @jax.jit
    def step(a, b):
        return jnp.sum(jnp.tanh(a @ b))

    a = jnp.asarray(np.random.RandomState(0).randn(64, 64).astype('f4'))
    step(a, a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        out = step(a, a)
    out.block_until_ready()
    jax.profiler.stop_trace()

    report = per_op_breakdown(str(tmp_path))
    assert report, 'no plane parsed from the trace'
    assert report['total_ns'] > 0
    assert report['by_category']
    # the two independent aggregations (by category, by op) must agree
    assert sum(ns for _, ns, _ in report['top_ops']) == \
        report['total_ns']
    assert report['top_ops'] and report['top_ops'][0][1] > 0
    text = format_breakdown(report)
    assert 'total' in text and '%' in text


def test_categorizer_uses_op_name_not_operands():
    """A fusion CONSUMING a custom-call's output must not be counted as
    a Pallas kernel (the exact miscategorization that skewed an early
    round-3 analysis)."""
    from autodist_tpu.utils.profiling import _categorize
    # FULL event names, operand lists included — the ' = ' head split
    # is the guard under test
    assert _categorize(
        '%fusion.1 = f32[64]{0} fusion(f32[64]{0} %custom-call.7), '
        'kind=kLoop') == 'fusion'
    assert _categorize(
        '%copy.12 = f32[8]{0} copy(f32[8]{0} %pallas_call.2)') == 'copy'
    assert _categorize('%pallas_call.3 = f32[2]{0} custom-call()') == \
        'pallas-kernel'
    assert _categorize('%custom-call.7') == 'pallas-kernel'
    assert _categorize('%multiply_reduce_fusion.2') == 'reduce-fusion'
    assert _categorize('%while.1 = (f32[2]{0}) while(%fusion.3)') == \
        'while(scan)'


def test_empty_dir_returns_empty(tmp_path):
    assert per_op_breakdown(str(tmp_path)) == {}
    assert format_breakdown({}) == '(no trace data)'


def test_corrupt_trace_degrades_to_empty(tmp_path):
    """ISSUE 2 satellite: a trace dir that exists but cannot be parsed
    (or has no matching timeline) must return an empty result with a
    logged warning, not raise — calibration degrades gracefully on
    CPU-fallback runs."""
    from autodist_tpu.utils.profiling import collective_timeline
    (tmp_path / 'bogus.xplane.pb').write_bytes(b'\x00not a real xplane')
    assert per_op_breakdown(str(tmp_path)) == {}
    assert collective_timeline(str(tmp_path)) == []


def test_missing_line_name_degrades_to_empty(tmp_path):
    """A real trace aggregated under a line name it does not contain
    must degrade to empty (device planes only carry 'XLA Ops')."""
    import jax as _jax

    @_jax.jit
    def step(a):
        return (a @ a).sum()

    a = jnp.asarray(np.random.RandomState(0).randn(16, 16).astype('f4'))
    step(a).block_until_ready()
    _jax.profiler.start_trace(str(tmp_path))
    step(a).block_until_ready()
    _jax.profiler.stop_trace()
    # a line name no plane carries: host fallback may still aggregate
    # SOMETHING (coarse program view) — the contract is "no raise, and
    # empty-or-dict", never an exception
    rep = per_op_breakdown(str(tmp_path), line_name='No Such Line')
    assert isinstance(rep, dict)


# -- report robustness (ISSUE 11 satellites) -------------------------------

def test_ps_overlap_report_zero_train_steps_is_empty():
    from autodist_tpu.utils.profiling import (format_ps_overlap,
                                              ps_overlap_report)
    assert ps_overlap_report({}) == {}
    assert ps_overlap_report(None) == {}
    assert ps_overlap_report({'pipeline': {'train_steps': 0}}) == {}
    # an eval-only session's stats (wire moved, zero train steps) must
    # not divide by the step count
    assert ps_overlap_report(
        {'bytes': 1024, 'seconds': 0.5,
         'pipeline': {'train_steps': 0, 'depth': 2}}) == {}
    assert format_ps_overlap({}) == '(no loose-mode train steps)'


def test_ps_overlap_report_tolerates_partial_snapshot():
    """A mid-replan / older-schema pipeline block missing fields must
    degrade to zeros and a computed overlap, never KeyError or
    ZeroDivisionError."""
    from autodist_tpu.utils.profiling import (format_ps_overlap,
                                              ps_overlap_report)
    rep = ps_overlap_report(
        {'pipeline': {'train_steps': 2, 'pull_s': 0.1,
                      'push_s': 0.1, 'exposed_wait_s': 0.05}})
    assert rep['wire_s'] == pytest.approx(0.2)
    assert rep['overlap_frac'] == pytest.approx(0.75)
    assert rep['depth'] == 1 and rep['step_s'] == 0.0
    # all-zero wire: overlap must be 0.0, not a division error
    rep = ps_overlap_report({'pipeline': {'train_steps': 3}})
    assert rep['wire_s'] == 0.0 and rep['overlap_frac'] == 0.0
    assert '(0.0ms exposed)' in format_ps_overlap(rep)


def test_health_report_tolerates_mid_replan_entries():
    """A snapshot taken while _execute_replan is mutating a replan
    entry (half-joined: flags without detail) must render, and the
    report's entry dicts must be COPIES (later mutation by the session
    thread cannot change the report under its consumer)."""
    from autodist_tpu.utils.profiling import format_health, health_report
    half1 = {'world': 3}                       # staged, nothing else
    half2 = {'world': 3, 'migrated': True}     # flag before detail
    half3 = {'world': 3, 'migration_staged': 'PS',
             'kept': 'PSLoadBalancing'}
    half4 = {'world': 3, 'migration_skipped': 'shard geometry'}
    hs = {'policy': 'exclude', 'generation': 0, 'epoch': 1,
          'missed_beats': 0, 'num_workers': 2, 'world': 3,
          'active_workers': 3,
          'exclusions': [{'worker': 'p1', 'epoch': 1}],
          'replans': [half1, half2, half3, half4],
          'joins': [{'worker': 'p2', 'epoch': 1}]}
    rep = health_report(hs)
    text = format_health(rep)
    assert 'MIGRATED to ?' in text            # placeholder, no crash
    assert 'migration staged: PS' in text
    assert 'migration skipped: shard geometry' in text
    # decoupled copies: mutating the session-side entry afterwards
    # must not reach into the already-taken report
    half2['migration'] = {'builder': 'X'}
    hs['exclusions'][0]['worker'] = 'pX'
    assert rep['replans'][1].get('migration') is None
    assert rep['exclusions'][0]['worker'] == 'p1'


def test_format_health_golden():
    """Golden rendering of a fully-populated health report: the lines
    operators grep in chaos triage must stay stable."""
    from autodist_tpu.utils.profiling import format_health
    report = {
        'policy': 'exclude', 'generation': 1, 'epoch': 2,
        'epoch_bumps': 2, 'num_workers': 2, 'world': 3,
        'active_workers': 2, 'missed_beats': 1,
        'exclusions': [{'worker': 'p1', 'epoch': 2}],
        'rejoins': ['p1'], 'recovery_wall_s': [1.5],
        'joins': [{'worker': 'p2', 'epoch': 1}],
        'admitted': {'worker': 'p2', 'epoch': 1,
                     'admit_wall_s': 0.004, 'adopted_step': 3},
        'replans': [{'world': 3, 'predicted': 'PS',
                     'kept': 'PSLoadBalancing'}],
        'autoscale': {'decisions': [{'action': 'scale_up'}],
                      'taken': 1, 'skipped': 0, 'failed': 0},
        'auto_checkpoints': 4, 'connect_retries': 7,
        'injected_faults': [{'kind': 'kill_worker', 'line': 'l1'}],
    }
    expected = '\n'.join([
        'policy=exclude generation=1 epoch=2  membership 2/2 (world 3)',
        '  missed beats: 1   connect retries: 7   auto-checkpoints: 4',
        '  joined as p2 at epoch 1 (admit 0.004s, adopted step 3)',
        '  observed join: p2 at epoch 1',
        '  replan @world=3: predicted PS vs kept PSLoadBalancing',
        '  autoscale: 1 taken / 0 skipped / 0 failed',
        '  excluded p1 at epoch 2',
        '  p1 rejoined after 1.5s',
        '  injected: kill_worker (l1)',
    ])
    assert format_health(report) == expected


def test_format_ps_overlap_golden():
    from autodist_tpu.utils.profiling import format_ps_overlap
    report = {'depth': 2, 'train_steps': 10, 'pull_s': 0.010,
              'step_s': 0.0301, 'push_s': 0.020, 'wire_s': 0.030,
              'exposed_wire_s': 0.0045, 'overlap_frac': 0.85}
    assert format_ps_overlap(report) == (
        'depth=2 steps=10  per-step: pull 10.0ms | step 30.1ms | '
        'push 20.0ms  wire 30.0ms (4.5ms exposed)  overlap 85%')
