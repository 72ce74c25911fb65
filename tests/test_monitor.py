"""Online performance sentry tests (ISSUE 12): phase splits shared
with trace_view, straggler verdicts (culprit vs upstream victim,
warm-up exclusion, single-worker cohorts, hysteresis), slowdown/
recovered flight events + conformance (incl. the truncated-ring
suppression rule), continuous recalibration changing a re-rank with
the audited constants, the autoscale metrics_source wiring, the
incremental batch collection cursor, and the telemetry-namespace
purge across back-to-back sessions on one service."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from autodist_tpu.analysis import conformance  # noqa: E402
from autodist_tpu.telemetry.monitor import (CohortMonitor,  # noqa: E402
                                            format_snapshot,
                                            phase_medians, phase_splits)


@pytest.fixture()
def flight():
    """A fresh flight recorder singleton for verdict-event tests."""
    from autodist_tpu import telemetry
    telemetry.reset_recorder()
    yield telemetry.recorder()
    telemetry.reset_recorder()


def _step_records(worker, steps, wall, gate=0.001, pull=0.0,
                  push=0.002, start=1, t0=1000.0):
    """Span records for `worker` over `steps` consecutive steps; wall/
    gate/pull/push may be callables of the step id."""
    out = []

    def val(v, st):
        return v(st) if callable(v) else v
    for st in range(start, start + steps):
        for name, v in (('step', wall), ('staleness_gate', gate),
                        ('pull_vars', pull), ('push_deltas', push)):
            d = val(v, st)
            if d <= 0:
                continue
            out.append({'name': name, 't0': t0 + st, 'dur': d,
                        'tags': {'step': st, 'worker': worker},
                        'worker': worker})
    return out


# -- phase splits: THE shared implementation -------------------------------

def test_phase_splits_and_compute_remainder():
    recs = _step_records('p0', 3, wall=0.010, gate=0.001, pull=0.002,
                         push=0.003)
    splits = phase_splits(recs)
    assert set(splits) == {'p0'}
    d = splits['p0'][1]
    assert d['step'] == pytest.approx(0.010)
    assert d['gate'] == pytest.approx(0.001)
    assert d['pull'] == pytest.approx(0.002)
    assert d['push'] == pytest.approx(0.003)
    # compute = step - measured phases, clamped at zero
    assert d['compute'] == pytest.approx(0.004)
    # records without a step tag or duration are skipped, not crashed
    assert phase_splits([{'name': 'step'}, {'name': 'rpc',
                                            'tags': {'cmd': 'INCR'}}]) \
        == {}


def test_phase_medians_warmup_exclusion():
    recs = _step_records('p0', 6, wall=lambda st: 1.0 if st <= 2
                         else 0.010)
    agg = phase_medians(recs, warmup_steps=2)
    assert agg['p0']['steps'] == 4
    assert agg['p0']['step'] == pytest.approx(0.010)
    # without the exclusion the compile-step outliers poison the median
    assert phase_medians(recs)['p0']['steps'] == 6


def test_trace_view_json_phases_pinned_to_monitor_helper(tmp_path):
    """The satellite pin: tools/trace_view.py --json must render the
    SAME per-phase aggregates the monitor computes — one
    implementation, one test, no drift."""
    recs = _step_records('p0', 5, wall=0.010) + \
        _step_records('p1', 5, wall=0.020, push=0.012)
    path = tmp_path / 'records.json'
    path.write_text(json.dumps(recs))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'trace_view.py'),
         str(path), '--json'],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout)
    assert summary['phases'] == phase_medians(recs)
    assert summary['phases']['p1']['push'] == pytest.approx(0.012)


# -- verdicts --------------------------------------------------------------

def test_culprit_detected_with_push_attribution(flight):
    mon = CohortMonitor(policy='advise', warmup_steps=1,
                        confirmations=1, flight=flight)
    mon.ingest(_step_records('p0', 10, wall=0.010))
    mon.ingest(_step_records('p1', 10, wall=lambda st: 0.010
                             if st < 5 else 0.060,
                             push=lambda st: 0.002 if st < 5
                             else 0.052))
    verdicts = mon.update_verdicts()
    assert len(verdicts) == 1
    v = verdicts[0]
    assert v['worker'] == 'p1' and v['statistic'] == 'work'
    assert v['attributed_phase'] == 'push'
    assert v['classification'] == 'link_or_host'
    assert v['exclude_candidate'] is True      # policy=advise
    assert v['phase_shares']['push'] > 0.8
    kinds = [e['kind'] for e in flight.events()]
    assert 'slowdown' in kinds
    # the ring replays conformant (slowdown needs no pairing)
    assert conformance.check_events(flight.events()) == []
    # recovery: the straggler speeds back up
    mon.ingest(_step_records('p1', 8, wall=0.010, start=11))
    mon.ingest(_step_records('p0', 8, wall=0.010, start=11))
    assert mon.update_verdicts() == []
    assert [e['kind'] for e in mon.events] == ['slowdown', 'recovered']
    assert conformance.check_events(flight.events()) == []
    # policy=warn issues verdicts but never exclude candidates
    warn = CohortMonitor(policy='warn', warmup_steps=1,
                         confirmations=1, flight=flight)
    warn.ingest(_step_records('p0', 8, wall=0.010))
    warn.ingest(_step_records('p1', 8, wall=0.060, push=0.052))
    (v,) = warn.update_verdicts()
    assert v['exclude_candidate'] is False


def test_warmup_steps_never_enter_baselines(flight):
    """The PR 6 lesson: a long recompile at the start must not read as
    straggling — steps at or below warmup_steps never enter any
    baseline."""
    mon = CohortMonitor(policy='warn', warmup_steps=3,
                        confirmations=1, flight=flight)
    mon.ingest(_step_records('p0', 10, wall=0.010))
    # p1's "slow" steps are all within warm-up; steady state is fast
    mon.ingest(_step_records('p1', 10, wall=lambda st: 2.0 if st <= 3
                             else 0.010))
    assert mon.update_verdicts() == []
    assert mon.worker_stats()['p1']['samples'] == 7


def test_single_worker_cohort_never_self_accuses(flight):
    mon = CohortMonitor(policy='advise', warmup_steps=0,
                        confirmations=1, flight=flight)
    mon.ingest(_step_records('p0', 12, wall=lambda st: 0.010 * st))
    assert mon.update_verdicts() == []
    assert len(mon.events) == 0


def test_policy_off_issues_nothing(flight):
    mon = CohortMonitor(policy='off', warmup_steps=1,
                        confirmations=1, flight=flight)
    mon.ingest(_step_records('p0', 8, wall=0.010))
    mon.ingest(_step_records('p1', 8, wall=0.060, push=0.052))
    assert mon.update_verdicts() == []
    assert flight.events() == []
    # statistics still collected (the autoscale signal stays live)
    assert mon.metrics()['step_time_s'] > 0


def test_victim_requires_culprit(flight):
    """A gate-dominated wall-slow worker is an upstream VICTIM — and a
    victim presupposes a culprit: with nobody work-slow (an input-
    bound cohort, everyone waiting on host tails) there is no verdict
    at all; with a work-slow culprit present, the victim verdict
    surfaces, classified upstream_victim and never an exclude
    candidate."""
    fast = dict(wall=0.006, gate=0.001, push=0.001)
    waiting = dict(wall=0.060, gate=0.055, push=0.001)
    # no culprit: 3 workers, one waiting on host tails -> silence
    mon = CohortMonitor(policy='advise', warmup_steps=0,
                        confirmations=1, flight=flight)
    mon.ingest(_step_records('p0', 8, **fast))
    mon.ingest(_step_records('p1', 8, **waiting))
    mon.ingest(_step_records('p3', 8, **fast))
    assert mon.update_verdicts() == []
    # same cohort + a genuinely work-slow p2: both verdicts issue
    mon2 = CohortMonitor(policy='advise', warmup_steps=0,
                         confirmations=1, flight=flight)
    mon2.ingest(_step_records('p0', 8, **fast))
    mon2.ingest(_step_records('p1', 8, **waiting))
    mon2.ingest(_step_records('p3', 8, **fast))
    mon2.ingest(_step_records('p2', 8, wall=0.060, gate=0.001,
                              push=0.052))
    by_worker = {v['worker']: v for v in mon2.update_verdicts()}
    assert by_worker['p2']['classification'] == 'link_or_host'
    assert by_worker['p2']['exclude_candidate'] is True
    assert by_worker['p1']['classification'] == 'upstream_victim'
    assert by_worker['p1']['exclude_candidate'] is False
    assert by_worker['p1']['attributed_phase'] == 'gate'


def test_hysteresis_suppresses_one_noisy_round(flight):
    """One noisy detection round (a GC pause window) must not fire a
    slowdown event; the same detection sustained over `confirmations`
    rounds must."""
    mon = CohortMonitor(policy='warn', warmup_steps=0,
                        confirmations=2, flight=flight)
    mon.ingest(_step_records('p0', 8, wall=0.010))
    mon.ingest(_step_records('p1', 8, wall=0.060, push=0.052))
    assert mon.update_verdicts() == []        # round 1: pending only
    assert len(mon.events) == 0
    # round 2 with the detection GONE: pending resets, nothing fires
    mon.ingest(_step_records('p1', 8, wall=0.010, start=9))
    mon.ingest(_step_records('p0', 8, wall=0.010, start=9))
    assert mon.update_verdicts() == []
    # sustained: two consecutive detections -> verdict
    mon.ingest(_step_records('p1', 6, wall=0.060, push=0.052,
                             start=17))
    mon.ingest(_step_records('p0', 6, wall=0.010, start=17))
    assert mon.update_verdicts() == []
    mon.ingest(_step_records('p1', 2, wall=0.060, push=0.052,
                             start=23))
    mon.ingest(_step_records('p0', 2, wall=0.010, start=23))
    assert len(mon.update_verdicts()) == 1
    assert [e['kind'] for e in mon.events] == ['slowdown']


def test_reset_baselines_clears_windows_and_verdicts(flight):
    mon = CohortMonitor(policy='warn', warmup_steps=0,
                        confirmations=1, flight=flight)
    mon.ingest(_step_records('p0', 8, wall=0.010))
    mon.ingest(_step_records('p1', 8, wall=0.060, push=0.052))
    assert mon.update_verdicts()
    mon.reset_baselines()
    assert mon.verdicts() == []
    assert mon.worker_stats() == {}


# -- conformance: the new event kinds --------------------------------------

def _ev(seq, kind, **fields):
    return dict({'seq': seq, 't': float(seq), 'wall': float(seq),
                 'kind': kind}, **fields)


def test_conformance_unmatched_recovery_and_truncation_rules():
    # paired slowdown -> recovered: clean
    assert conformance.check_events(
        [_ev(1, 'slowdown', worker='p1', step=5, phase='push'),
         _ev(2, 'recovered', worker='p1', step=9)]) == []
    # recovered with no prior slowdown on a COMPLETE ring: a finding
    fs = conformance.check_events(
        [_ev(1, 'step_publish', worker='p0', step=1),
         _ev(2, 'recovered', worker='p1', step=9)])
    assert len(fs) == 1 and 'unmatched-recovery' in fs[0]
    # the same on a TRUNCATED ring (first seq > 1): suppressed — the
    # opening slowdown may have scrolled off the bound
    assert conformance.check_events(
        [_ev(7, 'step_publish', worker='p0', step=1),
         _ev(8, 'recovered', worker='p1', step=9)]) == []
    # a retained run_start ENDS the truncation and re-arms the rule
    fs = conformance.check_events(
        [_ev(7, 'step_publish', worker='p0', step=1),
         _ev(8, 'run_start'),
         _ev(9, 'recovered', worker='p1', step=9)])
    assert len(fs) == 1 and 'unmatched-recovery' in fs[0]
    # a worker-less slowdown is malformed, reported not crashed
    fs = conformance.check_events([_ev(1, 'slowdown', step=5)])
    assert len(fs) == 1 and 'malformed-event' in fs[0]


def test_dump_with_slowdown_replays_through_analyze_cli(tmp_path,
                                                        flight):
    """ISSUE 12 acceptance: a dump carrying slowdown events replays
    conformant through tools/analyze.py --conformance; a doctored
    unmatched recovery is rejected naming the rule."""
    mon = CohortMonitor(policy='warn', warmup_steps=0,
                        confirmations=1, flight=flight)
    flight.record('run_start', ns='t')
    flight.record('step_publish', worker='p0', step=1)
    mon.ingest(_step_records('p0', 8, wall=0.010))
    mon.ingest(_step_records('p1', 8, wall=0.060, push=0.052))
    mon.update_verdicts()
    path = flight.dump('test', path=str(tmp_path / 'dump.json'))
    with open(path) as f:
        payload = json.load(f)
    assert any(e['kind'] == 'slowdown' for e in payload['events'])
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--conformance', path],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    # doctor: strip the slowdown, keep a fabricated recovered
    payload['events'] = [e for e in payload['events']
                         if e['kind'] != 'slowdown']
    payload['events'].append(_ev(payload['events'][-1]['seq'] + 1,
                                 'recovered', worker='p1', step=9))
    bad = tmp_path / 'doctored.json'
    bad.write_text(json.dumps(payload))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--conformance', str(bad)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=REPO)
    assert out.returncode == 1
    assert 'unmatched-recovery' in out.stdout


# -- continuous recalibration ----------------------------------------------

def _slow_link_samples(mon, n=16):
    """Measured transfers describing a SLOW link: ~0.5 GB/s with 10us
    setup — distinct sizes so the least-squares fit is well-posed."""
    for i in range(n):
        nbytes = 4096 * (1 + i % 4)
        mon.add_link_sample(nbytes, 1e-5 + nbytes * 2e-9)


def test_recalibration_changes_the_rerank_and_audit(flight,
                                                    monkeypatch):
    """ISSUE 12 acceptance: analytic constants pick plan A, live-refit
    constants pick plan B, and the replan audit records which
    constants priced it."""
    sys.path.insert(0, os.path.join(REPO, 'tests'))
    from test_simulator import make_gi, make_rs

    from autodist_tpu.simulator import search
    from autodist_tpu.simulator.cost_model import CostModelParams
    from autodist_tpu.strategy import builders as b

    gi = make_gi({'w': (1024, 1024), 'v': (512, 512)})
    # a FAST analytic hint (1 TB/s): the int8 tier's quantize cost
    # cannot pay for itself -> f32 wins on paper
    rs = make_rs(8, topology={'ici_bandwidth_gbps': 1000})
    analytic = CostModelParams.from_topology(rs.topology)
    cands = [('AllReduce(f32)', lambda: b.AllReduce(chunk_size=128)),
             ('AllReduce(int8-wire)',
              lambda: b.AllReduce(compressor='Int8RingCompressor'))]
    plan_a, _ = search.rank(gi, rs, candidates=list(cands),
                            params=analytic, num_replicas=8)
    assert plan_a[0].name == 'AllReduce(f32)'
    # the monitor refits from live link samples: the measured link is
    # ~0.5 GB/s — 2000x slower than the hint
    mon = CohortMonitor(policy='warn', flight=flight)
    _slow_link_samples(mon)
    measured = mon.recalibrate(analytic, num_replicas=8,
                               cross_node=False, step=40)
    assert measured is not None and measured.calibrated
    assert measured.beta_ici_s_per_byte > \
        100 * analytic.beta_ici_s_per_byte
    assert mon.recalibrations and \
        mon.recalibrations[0]['tier'] == 'ICI'
    plan_b, _ = search.rank(gi, rs, candidates=list(cands),
                            params=measured, num_replicas=8)
    assert plan_b[0].name == 'AllReduce(int8-wire)'   # the flip

    # the session's replan audit records WHICH constants priced it
    import types

    from autodist_tpu.runtime.session import Session
    stub = Session.__new__(Session)
    stub._plan = types.SimpleNamespace(
        strategy=types.SimpleNamespace(cost={'builder': 'PS'}),
        local_replicas=1)
    stub._cluster = types.SimpleNamespace(_resource_spec=rs)
    stub._graph_item = gi
    stub._loose = True
    stub._health = {'replans': []}
    stub._monitor = None
    monkeypatch.delenv('AUTODIST_EXECUTE_REPLAN', raising=False)
    stub._replan_for_world(8)
    entry_analytic = stub._health['replans'][-1]
    assert entry_analytic.get('error') is None, entry_analytic
    assert entry_analytic['cost_constants'] == 'analytic'
    stub._monitor = mon
    stub._replan_for_world(8)
    entry_measured = stub._health['replans'][-1]
    assert entry_measured.get('error') is None, entry_measured
    assert entry_measured['cost_constants'] == 'measured'
    assert entry_measured['cost_alpha_beta']['beta_s_per_byte'] == \
        pytest.approx(measured.beta_ici_s_per_byte)


def test_recalibration_degrades_gracefully(flight):
    mon = CohortMonitor(policy='warn', flight=flight)
    from autodist_tpu.simulator.cost_model import CostModelParams
    base = CostModelParams()
    # too few samples
    mon.add_link_sample(4096, 1e-4)
    assert mon.recalibrate(base) is None
    # degenerate: every sample the same size
    for _ in range(16):
        mon.add_link_sample(4096, 1e-4)
    assert mon.recalibrate(base) is None
    assert len(mon.recalibrations) == 0
    assert mon.calibrated_params(default=base) is base


# -- the autoscale signal --------------------------------------------------

def test_autoscale_metrics_source_wires_the_monitor(flight):
    from autodist_tpu.runtime.coordinator import (AutoscaleController,
                                                  autoscale_policy)
    mon = CohortMonitor(policy='warn', warmup_steps=0, flight=flight)
    launched = []
    ctl = AutoscaleController(
        autoscale_policy(step_time_target_s=0.02),
        scale_up=lambda n: launched.append(n) or n,
        current_world=2, max_workers=8,
        metrics_source=mon.metrics)
    # no samples yet: the policy has no signal, tick skips
    rec = ctl.tick()
    assert rec['action'] == 'skipped'
    # slow cohort: the monitor's measured step time trips the target
    for st in range(1, 6):
        mon.observe_step('p0', st, 0.05)
        mon.observe_step('p1', st, 0.05)
    rec = ctl.tick()
    assert rec['action'] == 'scale_up' and launched == [1]
    assert rec['metrics']['step_time_s'] == pytest.approx(0.05)
    # explicit per-tick metrics override the sampled source
    rec = ctl.tick(metrics={'step_time_s': 0.001})
    assert rec['action'] == 'skipped'


# -- live collection + the purge satellite ---------------------------------

def test_collect_new_records_cursor(service):
    from autodist_tpu.runtime.coord_client import CoordClient
    from autodist_tpu.telemetry import (collect_new_records,
                                        push_records)
    c = CoordClient(('127.0.0.1', service))
    try:
        ns = 'nscur'
        push_records(c, ns, 'p0',
                     [{'name': 'step', 't0': 1.0, 'dur': 0.01,
                       'tags': {'step': 1}}])
        cursor = {}
        first = collect_new_records(c, ns, ['p0', 'p1'], cursor)
        assert len(first) == 1 and cursor == {'p0': 1}
        # nothing new: nothing re-read
        assert collect_new_records(c, ns, ['p0', 'p1'], cursor) == []
        push_records(c, ns, 'p0',
                     [{'name': 'step', 't0': 2.0, 'dur': 0.01,
                       'tags': {'step': 2}}])
        second = collect_new_records(c, ns, ['p0', 'p1'], cursor)
        assert len(second) == 1
        assert second[0]['tags']['step'] == 2 and cursor == {'p0': 2}
        # the in-flight-push window: push_records bumps the counter
        # BEFORE the tensor write lands, so a poll racing it sees the
        # seq but no bytes — the cursor must NOT advance past the gap
        # (the batch would be dropped forever), and the next poll
        # picks it up once it lands
        c.incr('%s/telemetry/p0/batches' % ns, 1)       # seq 3, no b3
        assert collect_new_records(c, ns, ['p0'], cursor) == []
        assert cursor == {'p0': 2}                      # not advanced
        from autodist_tpu.telemetry import encode_records
        c.vset('%s/telemetry/p0/b3' % ns,
               encode_records([{'name': 'step', 't0': 3.0,
                                'dur': 0.01, 'tags': {'step': 3}}]),
               wire='f32')                              # now it lands
        late = collect_new_records(c, ns, ['p0'], cursor)
        assert len(late) == 1 and late[0]['tags']['step'] == 3
        assert cursor == {'p0': 3}
    finally:
        c.close()


def test_back_to_back_sessions_do_not_replay_stale_batches(
        service, monkeypatch, tmp_path):
    """The purge satellite: <ns>/telemetry/<worker>/b<seq> batch keys
    and the atomic batch counter must not survive run end even when
    the close-quorum purge never runs (a peer that crashed or never
    closed) — a reused service previously replayed run A's batches
    into run B's cohort trace."""
    import autodist_tpu as ad
    from autodist_tpu import telemetry
    from autodist_tpu.runtime.coord_client import CoordClient
    from autodist_tpu.utils.loose_harness import single_process_loose_env
    monkeypatch.setenv('AUTODIST_PEER_FAILURE_POLICY', 'fail')
    monkeypatch.setenv('AUTODIST_HEARTBEAT_TIMEOUT', '0')
    monkeypatch.setenv('AUTODIST_TELEMETRY', '1')
    monkeypatch.setenv('AUTODIST_TELEMETRY_DIR', str(tmp_path))
    monkeypatch.setenv('AUTODIST_TELEMETRY_PUSH_EVERY', '2')

    def run_once(tag):
        telemetry.reset()
        telemetry.reset_recorder()
        with single_process_loose_env(service, depth=1):
            autodist = ad.AutoDist(
                resource_info={'nodes': [
                    {'address': 'localhost', 'gpus': [0],
                     'chief': True, 'network_bandwidth': 100}]},
                strategy_builder=ad.strategy.PS(staleness=2))
            rng = np.random.RandomState(0)
            W0 = rng.randn(32, 2).astype(np.float32)
            feed = rng.randn(4, 32).astype(np.float32)
            with autodist.scope():
                x = ad.placeholder(shape=[None, 32],
                                   dtype=np.float32, name='x')
                W = ad.Variable(W0, name='W')
                loss = ad.ops.reduce_mean(
                    ad.ops.square(ad.ops.matmul(x, W)))
                train_op = ad.optimizers.SGD(0.01).minimize(loss, [W])
                autodist._build()
                ns = autodist._transformed[0].id

                def peer():
                    c = CoordClient(('127.0.0.1', service))
                    try:
                        gen = c.incr('fence/%s/p1' % ns, 0)
                        c.fence('fence/%s/p1' % ns, gen)
                        c.heartbeat('%s/p1' % ns)
                        c.barrier('%s/session/init' % ns, 2,
                                  timeout_s=60.0)
                        for st in range(1, 8):
                            c.publish_step('p1', st,
                                           prefix='%s/step/' % ns)
                        telemetry.push_records(
                            c, ns, 'p1',
                            [{'name': 'step', 't0': 1.0, 'dur': 0.01,
                              'tags': {'step': 1, 'run': tag}}])
                        c.set('done/%s/p1' % ns, '1')
                        c.publish_step('p1', 1 << 30,
                                       prefix='%s/step/' % ns)
                        # deliberately NO 'closed' bump: the purge
                        # quorum is never reached
                    finally:
                        c.close()

                t = threading.Thread(target=peer, daemon=True)
                t.start()
                sess = autodist.create_distributed_session()
                for _ in range(3):
                    sess.run(train_op, {x: feed})
                time.sleep(0.2)     # let the peer's batch land
                cohort = sess.cohort_telemetry()
                sess.close()
                t.join(timeout=20.0)
        telemetry.reset()
        return ns, cohort

    ns_a, cohort_a = run_once('A')
    # run A saw its own peer's batch
    assert any((r.get('tags') or {}).get('run') == 'A'
               for r in cohort_a)
    # after close, the telemetry namespace is GONE despite the purge
    # quorum never being reached: batch keys and the atomic counter
    c = CoordClient(('127.0.0.1', service))
    try:
        assert c.incr('%s/telemetry/p1/batches' % ns_a, 0) == 0
        assert c.vget('%s/telemetry/p1/b1' % ns_a, None) is None
        # seed a stale batch under run B's future namespace shape:
        # run_once uses a fresh AutoDist (fresh strategy id), so also
        # verify the chief INIT-clears a pre-seeded stale counter in
        # its own namespace path below
    finally:
        c.close()
    ns_b, cohort_b = run_once('B')
    # run B's cohort trace contains NOTHING of run A
    assert not any((r.get('tags') or {}).get('run') == 'A'
                   for r in cohort_b)
    assert any((r.get('tags') or {}).get('run') == 'B'
               for r in cohort_b)


def test_chief_init_clears_stale_telemetry_namespace(service,
                                                     monkeypatch,
                                                     tmp_path):
    """A crashed prior run whose close never ran leaves batch keys on
    a reused service: the chief deletes <ns>/telemetry/ BEFORE the
    init rendezvous, so the stale batches cannot replay even without
    a clean predecessor close."""
    import autodist_tpu as ad
    from autodist_tpu import telemetry
    from autodist_tpu.runtime.coord_client import CoordClient
    from autodist_tpu.utils.loose_harness import single_process_loose_env
    monkeypatch.setenv('AUTODIST_PEER_FAILURE_POLICY', 'fail')
    monkeypatch.setenv('AUTODIST_HEARTBEAT_TIMEOUT', '0')
    monkeypatch.setenv('AUTODIST_TELEMETRY', '1')
    monkeypatch.setenv('AUTODIST_TELEMETRY_DIR', str(tmp_path))
    telemetry.reset()
    telemetry.reset_recorder()
    with single_process_loose_env(service, depth=1):
        autodist = ad.AutoDist(
            resource_info={'nodes': [
                {'address': 'localhost', 'gpus': [0],
                 'chief': True, 'network_bandwidth': 100}]},
            strategy_builder=ad.strategy.PS(staleness=2))
        rng = np.random.RandomState(0)
        W0 = rng.randn(32, 2).astype(np.float32)
        feed = rng.randn(4, 32).astype(np.float32)
        with autodist.scope():
            x = ad.placeholder(shape=[None, 32], dtype=np.float32,
                               name='x')
            W = ad.Variable(W0, name='W')
            loss = ad.ops.reduce_mean(
                ad.ops.square(ad.ops.matmul(x, W)))
            train_op = ad.optimizers.SGD(0.01).minimize(loss, [W])
            autodist._build()
            ns = autodist._transformed[0].id
            # the crashed prior run's leftovers, seeded BEFORE the
            # session exists
            seeder = CoordClient(('127.0.0.1', service))
            telemetry.push_records(
                seeder, ns, 'p1',
                [{'name': 'step', 't0': 1.0, 'dur': 0.01,
                  'tags': {'step': 1, 'run': 'stale'}}])
            assert seeder.incr('%s/telemetry/p1/batches' % ns, 0) == 1

            def peer():
                c = CoordClient(('127.0.0.1', service))
                try:
                    gen = c.incr('fence/%s/p1' % ns, 0)
                    c.fence('fence/%s/p1' % ns, gen)
                    c.heartbeat('%s/p1' % ns)
                    c.barrier('%s/session/init' % ns, 2,
                              timeout_s=60.0)
                    for st in range(1, 6):
                        c.publish_step('p1', st,
                                       prefix='%s/step/' % ns)
                    c.set('done/%s/p1' % ns, '1')
                    c.publish_step('p1', 1 << 30,
                                   prefix='%s/step/' % ns)
                finally:
                    c.close()

            t = threading.Thread(target=peer, daemon=True)
            t.start()
            sess = autodist.create_distributed_session()
            assert seeder.incr('%s/telemetry/p1/batches' % ns, 0) == 0
            sess.run(train_op, {x: feed})
            cohort = sess.cohort_telemetry()
            assert not any((r.get('tags') or {}).get('run') == 'stale'
                           for r in cohort)
            sess.close()
            t.join(timeout=20.0)
            seeder.close()
    telemetry.reset()


# -- the CLI ---------------------------------------------------------------

def test_monitor_cli_offline_json(tmp_path):
    recs = _step_records('p0', 8, wall=0.010) + \
        _step_records('p1', 8, wall=0.060, push=0.052)
    path = tmp_path / 'records.json'
    path.write_text(json.dumps(recs))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'monitor.py'),
         str(path), '--json', '--policy', 'advise', '--warmup', '1'],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    snap = json.loads(out.stdout)
    assert set(snap['workers']) == {'p0', 'p1'}
    # the CLI runs single-shot (confirmations=1): the hysteresis that
    # protects the long-running chief must not eat its only round
    assert snap['verdicts'] and snap['verdicts'][0]['worker'] == 'p1'
    assert snap['verdicts'][0]['attributed_phase'] == 'push'
    # human rendering never crashes on the same snapshot
    assert 'VERDICT p1' in format_snapshot(snap)


def test_monitor_cli_rejects_non_record_input(tmp_path):
    path = tmp_path / 'dump.json'
    path.write_text(json.dumps({'events': []}))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'monitor.py'),
         str(path)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=REPO)
    assert out.returncode != 0
