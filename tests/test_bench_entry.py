"""The driver-facing entrypoints stay healthy: bench.py emits exactly
one valid JSON line on the CPU smoke path, and __graft_entry__.entry()
is jittable. (dryrun_multichip has its own driver run; re-running it
here would double the suite's longest compile.)
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_cpu_smoke_emits_one_json_line():
    env = dict(os.environ,
               JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=8')
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'bench.py')],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, out.stdout
    rec = json.loads(lines[0])
    for field in ('metric', 'value', 'unit', 'vs_baseline'):
        assert field in rec, rec
    assert rec['value'] > 0
    # the JSON carries the fields the perf trajectory needs (ISSUE 1):
    # platform, bucket count and per-step sync time
    extra = rec['extra']
    assert extra['platform'] == 'cpu'
    gs = extra['grad_sync']
    assert gs['bucket_count'] >= 1
    assert gs['per_step_sync_time_s'] > 0
    assert gs['sync_bytes'] > 0
    # ISSUE 2: every record carries the simulator block — the chosen
    # plan plus prediction AND measurement for each candidate run
    sim = extra['simulator']
    assert sim['chosen_strategy']
    assert sim['predicted_step_time_s'] > 0
    assert sim['predicted_peak_bytes'] > 0
    measured = [c for c in sim['candidates']
                if 'measured_step_time_s' in c]
    assert measured, sim['candidates']
    for c in measured:
        assert c['predicted_step_time_s'] > 0
        assert c['measured_step_time_s'] > 0
    assert any(c['name'].endswith('[auto]') for c in measured)
    # ISSUE 6: every record carries the elastic scale-up A/B — the live
    # JOIN really happened (admit wall time measured, membership grew)
    # and scaling mid-run left the math untouched
    el = extra['elastic']
    import shutil
    if shutil.which('g++'):   # no g++ = no coord service = degraded
        assert 'error' not in el, el
        assert el['world'] == 3 and el['joins_observed']
        assert el['admit_wall_s'] > 0
        assert el['state_max_abs_diff'] == 0.0
        assert el['replans']
    # PR 19: every record carries the epoch-swap A/B under its stable
    # key — the hand-staged PartitionedPS migration ran the full
    # handshake (gen staged, boundary armed, re-key moved bytes) and
    # the migration moved values, never recomputed them (0.0 diff;
    # -1.0 is the swap-never-landed sentinel)
    ep = extra['epoch_swap']
    if shutil.which('g++'):
        assert 'error' not in ep, ep
        assert ep['migrated'] is True, ep
        assert ep['swap_gen'] >= 1 and ep['swap_boundary'] >= 1, ep
        assert ep['steps_to_boundary'] >= 1, ep
        assert ep['rekeyed_vars'] >= 1, ep
        assert ep['bytes_resharded'] > 0, ep
        assert ep['state_max_abs_diff'] == 0.0, ep
    # ISSUE 17: every record carries the train-while-serve A/B under
    # its stable key — the replica fleet really served during training
    # (snapshots pulled, lookups answered) and every consistency gate
    # held: staleness within bound (guard +1, not the -1 sentinel),
    # zero torn mixed-version reads, and the final pinned snapshot
    # bit-exact against the session's authoritative read (f32 wire)
    sv = extra['serving']
    if shutil.which('g++'):
        assert 'error' not in sv, sv
        assert sv['replicas'] == 2, sv
        assert sv['alone']['per_step_wall_s'] > 0, sv
        assert sv['serving']['per_step_wall_s'] > 0, sv
        assert sv['serving']['snapshot_pulls'] >= 1, sv
        assert sv['serving']['lookups'] >= 1, sv
        assert sv['serving']['staleness_max_steps'] <= \
            sv['serving']['staleness_bound_steps'], sv
        assert sv['staleness_guard'] == 1.0, sv
        assert sv['mixed_version_reads'] == 0, sv
        assert sv['snapshot_divergence'] == 0.0, sv
        assert sv['trainer_slowdown'] > 0, sv
    # ISSUE 8: every record carries the quantized A/B under its stable
    # key — wire bytes measured >= 3x smaller on both data planes,
    # divergence bounded and reported
    q = extra['quantized']
    qg = q['grad_sync']
    assert 'error' not in qg, qg
    assert qg['bytes_reduction'] >= 3.0, qg
    assert qg['state_max_abs_diff'] < 0.05
    if shutil.which('g++'):
        qp = q['ps_push']
        assert 'error' not in qp, qp
        assert qp['push_bytes_reduction'] >= 3.0, qp
        assert qp['state_max_abs_diff'] < 0.05
    # ISSUE 9: every record carries the hierarchical A/B under its
    # stable key — the two-level schedule really emitted, it puts
    # ~g x fewer bytes on the DCN tier, and the synced gradients
    # diverge by at most f32 re-association noise
    h = extra['hierarchical']
    assert 'error' not in h, h
    assert h['two_level']['hier_buckets'] >= 1, h
    assert h['flat']['hier_buckets'] == 0, h
    assert h['dcn_bytes_reduction'] >= 3.0, h
    assert h['state_max_abs_diff'] < 1e-5, h
    # ISSUE 14: every record carries the weight-update-sharding A/B
    # under its stable key — the sharded schedule really emitted
    # (scatter+gather pair, every var update-sharded), it frees
    # >= 2x of the per-device opt-slot bytes at n >= 4 replicas with
    # state (vars AND slots) inside f32 re-association tolerance, and
    # the simulator's prediction for the sharded candidate rides the
    # record next to the measurement
    wu = extra['weight_update']
    assert 'error' not in wu, wu
    assert wu['devices'] >= 4, wu
    assert wu['sharded']['update_sharded_vars'] >= 1, wu
    assert wu['sharded']['reduce_scatter_wire_bytes'] > 0, wu
    assert wu['sharded']['all_gather_wire_bytes'] > 0, wu
    assert wu['replicated']['update_sharded_vars'] == 0, wu
    assert wu['opt_slot_bytes_reduction'] >= 2.0, wu
    assert wu['state_max_abs_diff'] < 1e-5, wu
    pred = wu['sharded']['predicted']
    assert pred['step_time_s'] > 0 and pred['peak_bytes'] > 0, wu
    assert pred['optimizer_bytes'] < \
        wu['replicated']['opt_slot_bytes_per_device'], wu
    # ISSUE 15: every record carries the roofline block under its
    # stable key — MFU explicit-null + reason on the CPU fallback
    # (never a number against an invented peak), the HBM
    # measured-vs-estimated drift join, and a per-entry
    # achieved-vs-predicted drift table whose entry ids round-trip to
    # the static collective schedule; the entry-labeled samples must
    # produce a non-degenerate calibration fit
    ro = extra['roofline']
    assert 'error' not in ro, ro
    assert ro['mfu'] is None and ro['mfu_null_reason'], ro
    assert ro['per_step_wall_s'] > 0
    assert ro['flops_per_step'] > 0
    assert ro['memory']['available'] is True, ro['memory']
    assert ro['memory']['classes']['state']['drift_ratio'] > 0
    dr = ro['drift']
    assert dr['entry_ids_roundtrip'] is True, dr
    assert dr['matched_rows'] >= 1 and dr['unmatched_rows'] == 0, dr
    assert dr['worst_drift_ratio'] > 0, dr
    joined = [r for r in dr['entries'] if r['achieved_s'] is not None]
    assert joined and all(r['predicted_s'] > 0 for r in joined), dr
    assert ro['calibration']['calibrated'] is True, ro['calibration']
    assert ro['tracker']['samples'] >= 1, ro['tracker']
    # ISSUE 11: every record carries the telemetry block under its
    # stable key — the on-vs-off overhead A/B, a multi-worker Chrome
    # trace whose step spans align on step ids, a clean conformance
    # replay and the simulator drift section
    tl = extra['telemetry']
    assert 'sim_drift' in tl, tl
    if shutil.which('g++'):
        assert 'error' not in tl, tl
        assert tl['telemetry_off']['per_step_wall_s'] > 0
        assert tl['telemetry_on']['per_step_wall_s'] > 0
        assert tl['overhead_frac'] <= tl['overhead_budget_frac'], tl
        tr = tl['trace']
        assert tr['events'] > 0 and len(tr['workers']) >= 2, tr
        assert tr['steps_aligned'], tr
        assert tl['conformance']['clean'], tl['conformance']
        assert tl['sim_drift'].get('candidates'), tl['sim_drift']
    # ISSUE 12: every record carries the monitor block under its
    # stable key — the injected delay_conn straggler detected with
    # push attribution within the step budget, ZERO false positives
    # on the clean leg, poll overhead inside the telemetry budget,
    # and a mid-slowdown flight dump that replays conformant
    mo = extra['monitor']
    if shutil.which('g++'):
        assert 'error' not in mo, mo
        assert mo['clean']['false_positive_verdicts'] == 0, mo
        st = mo['straggler']
        assert st['detected'] and st['verdict_worker'] == 'p1', st
        assert st['attributed_phase'] == 'push', st
        assert st['classification'] == 'link_or_host', st
        assert st['exclude_candidate'] is True, st
        assert 0 <= mo['detection_steps'] <= \
            mo['detection_budget_steps'], mo
        assert mo['overhead_frac'] <= mo['overhead_budget_frac'], mo
        assert mo['dump']['slowdown_events'] >= 1, mo['dump']
        assert mo['dump']['conformance_clean'], mo['dump']
    # ISSUE 13: every record carries the static-analysis trajectory
    # block under its stable key — the whole analyzer suite ran clean
    # with per-pass wall time and model-checker state counts, the
    # numbers bench_compare gates analyzer-cost/state-space blowup on
    an = extra['analysis']
    assert 'error' not in an, an
    assert an['clean'] is True and an['findings'] == 0, an
    assert an['schema_version'] >= 2, an
    assert an['total_elapsed_s'] > 0
    for p in ('protocol', 'data-plane', 'epoch-swap', 'fence', 'env',
              'schedule'):
        assert p in an['passes'], an['passes']
        assert an['passes'][p]['findings'] == 0, an['passes'][p]
    for p in ('protocol', 'data-plane', 'epoch-swap'):
        assert an['passes'][p]['states_explored'] > 100, an['passes'][p]
    assert an['states_explored_total'] >= sum(
        an['passes'][p]['states_explored']
        for p in ('protocol', 'data-plane', 'epoch-swap'))
    # ISSUE 20: the collective-schedule-IR A/B under its stable key —
    # candidates synthesized + shape-verified + priced, and the best
    # of each class actually executed on the mesh
    si = extra['schedule_ir']
    assert 'error' not in si, si
    assert si['devices'] == 8 and si['candidates'] > 0, si
    for side in ('handwritten', 'synthesized'):
        leg = si[side]
        assert leg['predicted_s'] > 0 and leg['tier_bytes'], leg
        assert leg['executed'] and leg['measured_per_step_s'] > 0, leg
        assert leg['verify_s'] >= 0 and leg['per_step_pred_s'], leg
    assert si['verify_total_s'] > 0, si
    # both legs synced the same seeded bucket: divergence is bounded
    # by one wire-quantization step, and -1 (a leg failed) must never
    # appear on a healthy mesh
    assert 0.0 <= si['state_max_abs_diff'] < 0.1, si


def _load_bench(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, 'bench.py'))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _stub_blocks(m, monkeypatch, failing=None):
    """Replace every ``bench_*`` block with a cheap stub; ``failing``
    names one that degrades to an ``{'error': ...}`` entry. With the
    cache variable set, ``main()`` leaves this process's jax config
    alone."""
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', os.devnull)
    for name in [n for n in dir(m) if n.startswith('bench_')]:
        monkeypatch.setattr(m, name, lambda *a, **kw: {})
    for name in ('bench_bert', 'bench_resnet101'):
        monkeypatch.setattr(m, name,
                            lambda *a, **kw: (1.0, 1.0, None, {}))
    if failing:
        monkeypatch.setattr(
            m, failing, lambda *a, **kw: {'error': 'RuntimeError: boom'})


def test_bench_block_error_exits_nonzero(monkeypatch, capsys):
    """A ``bench_*`` block that degrades to ``{'error': ...}`` must not
    hide behind a zero exit: the JSON line still prints (the record is
    kept) and ``main()`` exits non-zero naming the failed block."""
    m = _load_bench('bench_mod_err')
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
    monkeypatch.setattr(sys, 'argv', ['bench.py'])
    _stub_blocks(m, monkeypatch, failing='bench_serving')
    with pytest.raises(SystemExit) as exc:
        m.main()
    assert exc.value.code not in (0, None)
    assert 'extra.serving' in str(exc.value.code)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    assert json.loads(lines[0])['extra']['serving'] == \
        {'error': 'RuntimeError: boom'}
    # the same record with every block healthy exits normally
    _stub_blocks(m, monkeypatch)
    m.main()


def test_bench_refuses_unrequested_cpu(monkeypatch, capsys):
    """No silent CPU record: when JAX lands on the CPU without
    ``JAX_PLATFORMS=cpu`` having been given, ``main()`` fails before
    running a block or printing a line."""
    m = _load_bench('bench_mod_nocpu')
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    monkeypatch.setattr(sys, 'argv', ['bench.py'])
    _stub_blocks(m, monkeypatch)
    with pytest.raises(SystemExit) as exc:
        m.main()
    assert 'JAX_PLATFORMS=cpu' in str(exc.value.code)
    assert capsys.readouterr().out.strip() == ''


def test_bench_scaling_mode_reports_efficiency():
    """`bench.py --scaling` measures dp=1 vs dp=8 on the virtual mesh
    and reports both efficiency views (parallel + serialized-weak)."""
    m = _load_bench('bench_mod')
    rec = m.bench_scaling(steps=2)
    assert rec['extra']['devices'] == 8
    assert rec['value'] > 0
    assert rec['extra']['tokens_per_sec_per_chip_dp1'] > 0
    assert 0 < rec['extra']['parallel_efficiency'] <= 1.5
    # on the shared-core CPU mesh the dp lowering must not add gross
    # overhead over perfectly serialized compute
    assert rec['extra']['serialized_weak_scaling_efficiency'] > 0.5


def test_graft_entry_forward():
    import jax

    import __graft_entry__ as g
    fn, (params, tokens) = g.entry()
    logits = jax.jit(fn)(params, tokens)
    assert logits.shape[0] == tokens.shape[0]
    assert np.isfinite(np.asarray(logits)).all()
