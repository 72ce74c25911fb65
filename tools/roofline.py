"""Roofline observatory CLI — render MFU/regime, HBM drift and the
per-entry collective drift table from records or traces.

    # a record carrying an ``extra.roofline`` block (nothing in the
    # repository writes one since the host-side benchmark was retired:
    # ROADMAP.md queue 3 item 7)
    python tools/roofline.py record.json

    # a raw roofline block (``RooflineTracker.snapshot()``, or your own)
    python tools/roofline.py roofline.json --json

    # offline join: a profiler trace dir + the static schedule it ran
    # (JSON list of static_collective_schedule entries)
    python tools/roofline.py /tmp/trace --schedule sched.json \\
        --replicas 8

Inputs are sniffed per path: a JSON file carrying a ``roofline`` block
(wrapped under ``parsed`` or raw) or BEING one (a dict with ``drift`` /
``mfu`` keys) renders directly; a directory is treated as a captured
profiler trace whose collective timeline is joined against
``--schedule`` through the SAME ``telemetry.roofline.drift_table``
join the session's tracker uses. ``--json`` prints the machine-readable summary
(the tier-1 subprocess smoke's contract).
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault('JAX_PLATFORMS', 'cpu')


def _load_block(path):
    """A JSON file -> its roofline block, or None when the file is
    JSON but carries none."""
    with open(path) as f:
        payload = json.load(f)
    if isinstance(payload, dict) and isinstance(
            payload.get('parsed'), dict):
        payload = payload['parsed']
    if isinstance(payload, dict):
        block = (payload.get('extra') or {}).get('roofline')
        if isinstance(block, dict):
            return block
        if 'drift' in payload or 'mfu' in payload:
            return payload
    return None


def _render(block, as_json):
    from autodist_tpu.telemetry.roofline import format_drift_table
    if as_json:
        print(json.dumps(block, indent=2, sort_keys=True,
                         default=str))
        return
    mfu = block.get('mfu')
    if mfu is not None:
        print('MFU %.2f%%  regime=%s  hbm_frac=%s'
              % (100.0 * mfu, block.get('roofline_regime'),
                 block.get('hbm_frac')))
    else:
        print('MFU: null (%s)'
              % block.get('mfu_null_reason', 'no reason recorded'))
    mem = block.get('memory') or {}
    if mem.get('available'):
        print('HBM drift: measured %.1f MiB vs estimated %.1f MiB '
              '(ratio %s)'
              % (mem.get('measured_total_bytes', 0) / (1 << 20),
                 mem.get('estimated_total_bytes', 0) / (1 << 20),
                 mem.get('drift_ratio')))
        for cls, rec in sorted((mem.get('classes') or {}).items()):
            print('  %-10s measured %.1f MiB vs estimated %.1f MiB '
                  '(ratio %s)'
                  % (cls, rec['measured_bytes'] / (1 << 20),
                     rec['estimated_bytes'] / (1 << 20),
                     rec['drift_ratio']))
    elif mem:
        print('HBM drift: unavailable (%s)' % mem.get('reason'))
    drift = block.get('drift') or {}
    if drift.get('entries'):
        print(format_drift_table(drift))
        if 'entry_ids_roundtrip' in drift:
            print('entry ids round-trip to the static schedule: %s'
                  % drift['entry_ids_roundtrip'])


def _join_trace(trace_dir, schedule_path, replicas, multi_node):
    from autodist_tpu.simulator.calibrate import calibrate_from_drift
    from autodist_tpu.simulator.cost_model import CostModelParams
    from autodist_tpu.telemetry.roofline import drift_table
    from autodist_tpu.utils.profiling import collective_timeline
    with open(schedule_path) as f:
        schedule = json.load(f)
    if not isinstance(schedule, list):
        raise ValueError('%s: not a schedule entry list'
                         % schedule_path)
    timeline = collective_timeline(
        trace_dir, expected_collectives=len(schedule))
    table = drift_table(schedule, timeline, replicas,
                        params=CostModelParams(),
                        multi_node=multi_node)
    refit = calibrate_from_drift(CostModelParams(), table, replicas)
    return {'drift': {k: v for k, v in table.items()
                      if k != 'samples'},
            'calibration': {'calibrated': bool(refit.calibrated),
                            'alpha_ici_s': refit.alpha_ici_s,
                            'beta_ici_s_per_byte':
                                refit.beta_ici_s_per_byte,
                            'alpha_dcn_s': refit.alpha_dcn_s,
                            'beta_dcn_s_per_byte':
                                refit.beta_dcn_s_per_byte}}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='render roofline records / join a trace against '
                    'its static collective schedule')
    ap.add_argument('paths', nargs='+',
                    help='records, roofline blocks, or a '
                         'profiler trace dir (with --schedule)')
    ap.add_argument('--schedule',
                    help='static_collective_schedule entries (JSON '
                         'list) for trace-dir inputs')
    ap.add_argument('--replicas', type=int, default=2,
                    help='replica count a trace-dir join prices '
                         'against (default 2)')
    ap.add_argument('--multi-node', action='store_true',
                    help='price flat entries on the DCN tier')
    ap.add_argument('--json', action='store_true',
                    help='print machine-readable blocks')
    args = ap.parse_args(argv)
    rendered = 0
    for path in args.paths:
        if os.path.isdir(path):
            if not args.schedule:
                print('roofline: %s is a trace dir — pass --schedule '
                      'with its static collective schedule' % path,
                      file=sys.stderr)
                return 2
            block = _join_trace(path, args.schedule, args.replicas,
                                args.multi_node)
        else:
            block = _load_block(path)
            if block is None:
                print('roofline: %s carries no roofline block'
                      % path, file=sys.stderr)
                continue
        if rendered and not args.json:
            print('-' * 60)
        _render(block, args.json)
        rendered += 1
    if not rendered:
        print('roofline: no renderable input', file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
