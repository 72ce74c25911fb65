"""Diff two BENCH records per stable key — the machine-readable half
of the bench trajectory.

    python tools/bench_compare.py old_record.json new_record.json
    python tools/bench_compare.py OLD.json NEW.json --threshold 0.15
    python tools/bench_compare.py OLD.json NEW.json --json

Each BENCH_r*.json is either the driver wrapper (``{'parsed': {...}}``)
or bench.py's raw output line. The comparison walks a curated metric
table grouped by the stable record keys (grad_sync, quantized,
hierarchical, weight_update, elastic, ps_pipeline, local_sgd,
telemetry, monitor, analysis, roofline, top-level throughput) with a
per-metric
direction; a NEW value worse
than OLD by
more than ``--threshold`` (fractional, default 0.10) is a REGRESSION.
Metrics missing from either record are reported as skipped, never
fatal — older records predate newer keys.

Cross-platform comparisons are REFUSED (exit 2): records carry
``extra.platform``, and a CPU-smoke number regressing against a TPU
number is noise wearing a trend costume. ``--allow-cross-platform``
overrides for exploratory use.

Exit codes: 0 = no regression, 1 = regression(s), 2 = unusable input /
platform refusal.
"""
import argparse
import json
import sys

#: (stable key, dotted path, direction, label). Direction 'lower' =
#: smaller is better (times, overhead), 'higher' = bigger is better
#: (throughput, reduction ratios, overlap).
METRICS = (
    ('top', 'value', 'higher', 'headline throughput'),
    ('grad_sync', 'extra.grad_sync.per_step_sync_time_s', 'lower',
     'per-step grad sync time'),
    ('grad_sync', 'extra.grad_sync.sync_wire_bytes', 'lower',
     'grad sync wire bytes'),
    ('quantized', 'extra.quantized.grad_sync.bytes_reduction', 'higher',
     'int8 grad-sync wire reduction'),
    ('quantized', 'extra.quantized.ps_push.push_bytes_reduction',
     'higher', 'int8 PS push-byte reduction'),
    ('hierarchical', 'extra.hierarchical.dcn_bytes_reduction', 'higher',
     'two-level DCN byte reduction'),
    ('weight_update', 'extra.weight_update.opt_slot_bytes_reduction',
     'higher', 'weight-update opt-slot memory reduction'),
    ('weight_update', 'extra.weight_update.sharded.per_step_wall_s',
     'lower', 'sharded-update per-step wall'),
    ('weight_update',
     'extra.weight_update.sharded.all_gather_wire_bytes', 'lower',
     'weight-update param all-gather wire bytes'),
    ('elastic', 'extra.elastic.admit_wall_s', 'lower',
     'elastic admit wall time'),
    ('elastic', 'extra.elastic.steps_blocked', 'lower',
     'steps blocked by the join'),
    # the epoch-swap trajectory (PR 19): bytes_resharded is
    # deterministic byte accounting of the re-key; downtime and
    # steps-to-boundary are handshake-latency counters over one-shot
    # thread-timed runs, so they carry the wide 5x scale. A
    # state_max_abs_diff of -1 is the failure sentinel (the migration
    # never landed); otherwise the moved-not-recomputed claim makes it
    # exactly 0.0 and the zero-baseline epsilon catches the first
    # divergent bit.
    ('epoch_swap', 'extra.epoch_swap.bytes_resharded', 'lower',
     'epoch-swap re-key wire bytes'),
    ('epoch_swap', 'extra.epoch_swap.swap_downtime_steps', 'lower',
     'steps stalled by the epoch swap', 5),
    ('epoch_swap', 'extra.epoch_swap.steps_to_boundary', 'lower',
     'epoch-swap request-to-boundary steps', 5),
    ('epoch_swap', 'extra.epoch_swap.state_max_abs_diff', 'lower',
     'epoch-swap final-state divergence vs control (-1 = no swap)'),
    ('ps_pipeline', 'extra.ps_pipeline.depth2.overlap_frac', 'higher',
     'PS pipeline depth-2 overlap fraction'),
    ('ps_pipeline', 'extra.ps_pipeline.depth2_speedup', 'higher',
     'PS pipeline depth-2 speedup'),
    # the local-SGD window trajectory (ISSUE 16): the wire-bytes
    # ratio is deterministic byte accounting (~H by construction, so
    # it gates at the normal threshold); the per-step walls are
    # injected-delay-dominated one-shot timings and the divergence is
    # float noise around 0 — both carry the wide 5x scale. A
    # divergence of -1 would be the failure sentinel (legs did not
    # both finish); the sentinel rule below handles it.
    ('local_sgd', 'extra.local_sgd.wire_bytes_ratio', 'higher',
     'local-SGD H=8 wire-bytes reduction'),
    ('local_sgd', 'extra.local_sgd.wall_speedup', 'higher',
     'local-SGD H=8 weak-link wall speedup', 5),
    ('local_sgd', 'extra.local_sgd.h8.per_step_wall_s', 'lower',
     'local-SGD H=8 per-step wall', 5),
    ('local_sgd', 'extra.local_sgd.divergence', 'lower',
     'local-SGD H=8 final-state divergence', 5),
    # the train-while-serve trajectory (ISSUE 17): the slowdown ratio
    # and lookup latencies are one-shot concurrent-thread timings
    # (scheduler-noise dominated), so they carry the wide 5x scale.
    # The three consistency gates are deterministic: staleness_guard
    # is +1/-1 (-1 = a replica accepted a snapshot past its staleness
    # bound — the failure-sentinel rule fires), mixed_version_reads
    # counts torn snapshots (must stay 0; the zero-baseline epsilon
    # catches the first one appearing), and snapshot_divergence is
    # bit-exactness of the final pinned snapshot on the f32 wire.
    ('serving', 'extra.serving.trainer_slowdown', 'lower',
     'train-while-serve trainer slowdown ratio', 5),
    ('serving', 'extra.serving.serving.lookup_p99_ms', 'lower',
     'serving lookup p99 latency', 5),
    ('serving', 'extra.serving.serving.qps', 'higher',
     'serving fleet lookup throughput', 5),
    ('serving', 'extra.serving.staleness_guard', 'higher',
     'serving staleness-bound guard (-1 = bound violated)'),
    ('serving', 'extra.serving.mixed_version_reads', 'lower',
     'serving torn-snapshot reads'),
    ('serving', 'extra.serving.snapshot_divergence', 'lower',
     'serving final-snapshot divergence vs authoritative read'),
    ('telemetry', 'extra.telemetry.overhead_frac', 'lower',
     'telemetry overhead fraction'),
    ('monitor', 'extra.monitor.detection_steps', 'lower',
     'straggler detection latency (steps)'),
    ('monitor', 'extra.monitor.clean.false_positive_verdicts', 'lower',
     'clean-leg false positives'),
    ('monitor', 'extra.monitor.overhead_frac', 'lower',
     'monitor poll overhead fraction'),
    # the static-analysis trajectory: analyzer wall cost and model-
    # checker state-space size are both tier-1 budget items — a pass
    # that quietly doubles its exploration is a regression even at
    # zero findings. The wall times are SINGLE-SHOT subprocess
    # measurements (interpreter + import dominated), so they carry a
    # 5x threshold scale: a real blowup roughly doubles them, machine
    # noise does not move them 50%. The deterministic states counts
    # gate at the normal threshold.
    ('analysis', 'extra.analysis.total_elapsed_s', 'lower',
     'static-analysis total wall time', 5),
    ('analysis', 'extra.analysis.states_explored_total', 'lower',
     'model-checker states explored (all passes)'),
    ('analysis', 'extra.analysis.passes.protocol.elapsed_s', 'lower',
     'protocol model-checker wall time', 5),
    ('analysis', 'extra.analysis.passes.data-plane.states_explored',
     'lower', 'data-plane model states explored'),
    ('analysis', 'extra.analysis.passes.epoch-swap.states_explored',
     'lower', 'epoch-swap model states explored'),
    # the device-plane roofline trajectory (ISSUE 15): MFU is the
    # headline (json-null on the CPU fallback -> skipped; -1 = the
    # measurement itself failed = failure sentinel, regression);
    # per-tier achieved bandwidth and the drift ratios gate the cost
    # model's honesty. The microbench-sourced numbers are noisy
    # single-host timings, so the drift ratios carry a wide scale.
    ('roofline', 'extra.roofline.mfu', 'higher', 'per-step MFU'),
    ('roofline', 'extra.roofline.drift.tiers.ici.achieved_bytes_per_s',
     'higher', 'ICI achieved bytes/s (per-entry join)', 5),
    ('roofline', 'extra.roofline.drift.tiers.dcn.achieved_bytes_per_s',
     'higher', 'DCN achieved bytes/s (per-entry join)', 5),
    ('roofline', 'extra.roofline.memory.abs_drift', 'lower',
     'HBM estimate drift |ratio-1|', 5),
    ('roofline', 'extra.roofline.drift.worst_drift_ratio', 'lower',
     'worst per-entry collective drift', 5),
    # the collective-schedule-IR trajectory (ISSUE 20): the predicted
    # speedup, per-tier bytes, and verification wall are deterministic
    # cost-model/shape-algebra outputs (normal threshold; the verify
    # wall is sub-millisecond interpreter work, so it rides the wide
    # scale anyway); the measured per-step syncs are CPU-mesh
    # collective timings (5x scale). state_max_abs_diff is the
    # synth-vs-hand synced-state divergence — seeded grads make the
    # wire-quantization error deterministic, and -1 is the failure
    # sentinel (a leg never produced a synced state).
    ('schedule_ir', 'extra.schedule_ir.predicted_speedup', 'higher',
     'synthesized-vs-hand-written predicted schedule speedup'),
    ('schedule_ir', 'extra.schedule_ir.synthesized.tier_bytes.dcn',
     'lower', 'synthesized-best DCN bytes per step'),
    ('schedule_ir', 'extra.schedule_ir.verify_total_s', 'lower',
     'schedule-IR verification wall (all candidates)', 5),
    ('schedule_ir',
     'extra.schedule_ir.handwritten.measured_per_step_s', 'lower',
     'hand-written-best measured per-step sync', 5),
    ('schedule_ir',
     'extra.schedule_ir.synthesized.measured_per_step_s', 'lower',
     'synthesized-best measured per-step sync', 5),
    ('schedule_ir', 'extra.schedule_ir.state_max_abs_diff', 'lower',
     'synth-vs-hand synced-state divergence (-1 = leg failed)'),
)


def load_record(path):
    """A BENCH file -> the bench.py result dict (unwrapping the
    driver's ``{'parsed': ...}`` envelope). Raises ValueError when
    neither shape fits."""
    with open(path) as f:
        payload = json.load(f)
    if isinstance(payload, dict) and isinstance(
            payload.get('parsed'), dict):
        payload = payload['parsed']
    if not isinstance(payload, dict) or 'metric' not in payload:
        raise ValueError(
            '%s: not a BENCH record (no parsed bench result with a '
            "'metric' field — rc!=0 runs carry parsed=null)" % path)
    return payload


def _lookup(record, path):
    cur = record
    for part in path.split('.'):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) and not isinstance(
        cur, bool) else None


def compare(old, new, threshold=0.10):
    """Walk the metric table; returns the report dict."""
    rows = []
    regressions = 0
    for entry in METRICS:
        key, path, direction, label = entry[:4]
        # optional 5th element: per-metric threshold scale (noisy
        # one-shot wall times gate wider than deterministic counts)
        scale = entry[4] if len(entry) > 4 else 1
        a, b = _lookup(old, path), _lookup(new, path)
        row = {'key': key, 'metric': path, 'label': label,
               'direction': direction, 'old': a, 'new': b}
        if a is None or b is None:
            row['status'] = 'skipped'
            row['note'] = 'missing in %s record' % (
                'both' if a is None and b is None
                else ('old' if a is None else 'new'))
        elif a < 0 or b < 0:
            # a negative value is a FAILURE SENTINEL in BOTH
            # directions (PR 11 rule, extended for the roofline
            # metrics' null/-1 convention): lower-is-better, -1 would
            # read as the best possible value (detection_steps=-1 =
            # never detected); higher-is-better, -1 marks "the
            # measurement itself failed" distinct from json-null
            # ("legitimately unavailable", which skips above)
            if b < 0:
                row['status'] = 'regression'
                row['note'] = ('failure sentinel in new record '
                               '(%g): the measurement itself failed'
                               % b)
                regressions += 1
            else:
                row['status'] = 'ok'
                row['note'] = ('old record carries a failure '
                               'sentinel (%g); any measured new '
                               'value is an improvement' % a)
        else:
            if direction == 'lower':
                # worse = bigger; ratio vs the old value, with an
                # absolute epsilon so 0 -> 0.0001 (a count appearing)
                # still registers against a zero baseline
                worse = (b - a) / a if a else (1.0 if b > 1e-12 else 0.0)
            else:
                worse = (a - b) / a if a else 0.0
            row['delta_frac'] = round(worse, 4)
            row['status'] = ('regression'
                             if worse > threshold * scale else 'ok')
            if row['status'] == 'regression':
                regressions += 1
        rows.append(row)
    return {'threshold': threshold, 'rows': rows,
            'regressions': regressions, 'clean': regressions == 0}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='diff two BENCH records per stable key; nonzero '
                    'exit on regression')
    ap.add_argument('old')
    ap.add_argument('new')
    ap.add_argument('--threshold', type=float, default=0.10,
                    help='fractional regression threshold (default '
                         '0.10 = 10%%)')
    ap.add_argument('--allow-cross-platform', action='store_true',
                    help='compare records from different platforms '
                         'anyway (normally refused)')
    ap.add_argument('--json', action='store_true',
                    help='print the machine-readable report')
    args = ap.parse_args(argv)
    try:
        old = load_record(args.old)
        new = load_record(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print('bench_compare: %s' % e, file=sys.stderr)
        return 2
    p_old = (old.get('extra') or {}).get('platform')
    p_new = (new.get('extra') or {}).get('platform')
    if p_old and p_new and p_old != p_new and \
            not args.allow_cross_platform:
        print('bench_compare: REFUSED — %s is a %r record, %s is %r; '
              'cross-platform deltas are noise, not a trend '
              '(--allow-cross-platform to override)'
              % (args.old, p_old, args.new, p_new), file=sys.stderr)
        return 2
    report = compare(old, new, threshold=args.threshold)
    report['platform'] = p_new or p_old
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for row in report['rows']:
            if row['status'] == 'skipped':
                print('  skip  %-38s (%s)' % (row['label'], row['note']))
                continue
            mark = {'ok': '  ok  ', 'regression': 'REGR  '}[row['status']]
            if 'delta_frac' not in row:   # failure-sentinel rows
                print('%s%-38s %12.6g -> %-12.6g (%s)'
                      % (mark, row['label'], row['old'], row['new'],
                         row['note']))
                continue
            print('%s%-38s %12.6g -> %-12.6g (%+.1f%% worse, %s '
                  'better)' % (mark, row['label'], row['old'],
                               row['new'], 100 * row['delta_frac'],
                               row['direction']))
        print('bench_compare %s: %d regression(s) at threshold %.0f%%'
              % ('CLEAN' if report['clean'] else 'FAILED',
                 report['regressions'], 100 * args.threshold))
    return 0 if report['clean'] else 1


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
