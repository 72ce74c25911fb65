"""Unified telemetry plane (ISSUE 11): span/metrics registry, PS-plane
aggregation + Chrome export, the crash flight recorder, and the chaos
acceptance — a kill-1-under-exclude run produces a flight-recorder
dump whose replayed trace passes protocol conformance.

Registry/encoding/export tests are pure-Python; everything touching
the coord service is g++-gated like the other native-plane suites.
"""
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_gpp = pytest.mark.skipif(shutil.which('g++') is None,
                               reason='g++ unavailable')


@pytest.fixture()
def telem(monkeypatch, tmp_path):
    """A fresh ENABLED telemetry singleton + flight recorder, torn
    down after the test so the suite's default stays zero-cost."""
    from autodist_tpu import telemetry
    monkeypatch.setenv('AUTODIST_TELEMETRY', '1')
    monkeypatch.setenv('AUTODIST_TELEMETRY_DIR', str(tmp_path))
    telemetry.reset()
    telemetry.reset_recorder()
    yield telemetry
    telemetry.reset()
    telemetry.reset_recorder()


# -- registry --------------------------------------------------------------

def test_disabled_is_noop_and_allocation_free(monkeypatch):
    from autodist_tpu import telemetry
    from autodist_tpu.telemetry.core import _NULL_SPAN
    monkeypatch.delenv('AUTODIST_TELEMETRY', raising=False)
    telemetry.reset()
    tel = telemetry.get()
    assert not tel.enabled
    # the SAME shared null context manager every call: no per-span
    # allocation on the disabled path
    assert tel.span('step', step=1) is _NULL_SPAN
    assert tel.span('other') is _NULL_SPAN
    with tel.span('step', step=1):
        pass
    tel.count('c')
    tel.gauge('g', 1.0)
    tel.observe('s', 0.5)
    tel.event('e')
    snap = tel.metrics_snapshot()
    assert snap['counters'] == {} and snap['series'] == {}
    assert tel.drain_spans() == []
    telemetry.reset()


def test_enabled_records_spans_counters_series(telem):
    tel = telem.get()
    assert tel.enabled
    with tel.span('push_deltas', step=3, worker='p0'):
        time.sleep(0.002)
    tel.count('rpc', 2)
    tel.gauge('step', 3)
    tel.observe('step_wall_s', 0.01)
    tel.observe('step_wall_s', 0.03)
    tel.event('bucket_emit', schedule='flat', wire='f32')
    snap = tel.metrics_snapshot()
    assert snap['spans']['push_deltas']['count'] == 1
    assert snap['spans']['push_deltas']['mean_s'] >= 0.002
    assert snap['counters'] == {'rpc': 2}
    assert snap['gauges'] == {'step': 3}
    s = snap['series']['step_wall_s']
    assert s['count'] == 2 and abs(s['mean'] - 0.02) < 1e-9
    recs = tel.drain_spans()
    names = {r['name'] for r in recs}
    assert names == {'push_deltas', 'bucket_emit'}
    span = next(r for r in recs if r['name'] == 'push_deltas')
    assert span['tags'] == {'step': 3, 'worker': 'p0'}
    assert span['dur'] >= 0.002 and span['t0'] > 0
    # drained: the buffer is empty now
    assert tel.drain_spans() == []
    # span aggregates are CUMULATIVE: a drain (the periodic batch
    # push) must not reset the snapshot's per-name counts
    with tel.span('push_deltas', step=4, worker='p0'):
        pass
    snap2 = tel.metrics_snapshot()
    assert snap2['spans']['push_deltas']['count'] == 2


def test_span_buffers_are_bounded(monkeypatch):
    from autodist_tpu import telemetry
    monkeypatch.setenv('AUTODIST_TELEMETRY', '1')
    monkeypatch.setenv('AUTODIST_TELEMETRY_MAX_SPANS', '64')
    telemetry.reset()
    tel = telemetry.get()
    for i in range(500):
        tel.record_span('s', 0.0, 0.001, i=i)
        tel.observe('w', float(i))
    assert len(tel.drain_spans()) == 64
    # the series ring drops old values but count/total survive
    snap = tel.metrics_snapshot()
    assert snap['series']['w']['count'] == 500
    assert tel.series_values('w')[-1] == 499.0
    telemetry.reset()


def test_span_records_error_tag(telem):
    tel = telem.get()
    with pytest.raises(ValueError):
        with tel.span('step', step=1):
            raise ValueError('boom')
    (rec,) = tel.drain_spans()
    assert rec['tags']['error'] == 'ValueError'


# -- wire encoding + chrome export -----------------------------------------

def test_record_encoding_roundtrip():
    from autodist_tpu.telemetry import decode_records, encode_records
    for records in (
            [],
            [{'name': 'step', 't0': 1.5, 'dur': 0.25,
              'tags': {'step': 1, 'worker': 'p0'}}],
            [{'name': 'ünïcode', 't0': 0.0}] * 7,   # non-4-divisible
    ):
        enc = encode_records(records)
        assert enc.dtype == np.float32
        assert decode_records(enc) == records
    assert decode_records(None) == []
    # the length cell is a u32 REINTERPRETED as float32: a float-
    # valued length would lose integer precision past 2^24 bytes and
    # silently corrupt any batch over 16 MiB
    import struct
    enc = encode_records([{'name': 'x'}])
    n = struct.unpack('<I', enc[:1].tobytes())[0]
    assert n == len(json.dumps([{'name': 'x'}],
                               separators=(',', ':')))


def test_chrome_trace_shape_and_step_alignment():
    from autodist_tpu.telemetry import chrome_trace, step_timeline
    records = [
        {'name': 'step', 't0': 10.0, 'dur': 0.05, 'worker': 'p0',
         'tags': {'step': 1, 'worker': 'p0'}},
        {'name': 'step', 't0': 10.01, 'dur': 0.04, 'worker': 'p1',
         'tags': {'step': 1, 'worker': 'p1'}},
        {'name': 'bucket_emit', 't0': 10.02, 'worker': 'p0',
         'tags': {'schedule': 'flat'}},
    ]
    # worker_self = the ACTOR's row; 'worker' is the event's SUBJECT
    # (e.g. the excluded peer) and must not decide placement
    flight = [{'seq': 1, 'kind': 'step_publish', 'wall': 10.06,
               'worker': 'p1', 'worker_self': 'p0', 'step': 1}]
    trace = chrome_trace(records, flight_events=flight)
    evs = trace['traceEvents']
    meta = [e for e in evs if e['ph'] == 'M']
    assert {m['args']['name'] for m in meta} == \
        {'worker p0', 'worker p1'}
    spans = [e for e in evs if e['ph'] == 'X']
    assert {e['pid'] for e in spans} == {0, 1}
    # aligned on step ids: the span args carry the step
    assert all(e['args']['step'] == 1 for e in spans)
    instants = [e for e in evs if e['ph'] == 'i']
    assert {e['name'] for e in instants} == \
        {'bucket_emit', 'step_publish'}
    (fl_ev,) = [e for e in instants if e['name'] == 'step_publish']
    assert fl_ev['pid'] == 0   # the actor's row, not the subject's
    # timestamps are relative microseconds, non-negative
    assert all(e['ts'] >= 0 for e in spans + instants)
    tl = step_timeline(records)
    assert tl == {1: {'p0': 0.05, 'p1': 0.04}}
    # a flight-events-only trace (trace_view fed dump files, no span
    # batches) must still be zero-origined, not raw-epoch timestamps
    only_flight = chrome_trace([], flight_events=flight)
    (ev,) = only_flight['traceEvents']
    assert ev['ts'] == 0.0


def test_stub_session_property_errors_are_not_masked():
    """The stub-session fallback is a non-data descriptor, NOT
    __getattr__: an AttributeError escaping a real property getter
    must name the actually-missing attribute, and unknown attributes
    still raise normally."""
    from autodist_tpu import telemetry
    from autodist_tpu.runtime.session import Session
    stub = Session.__new__(Session)
    assert stub._tel is telemetry.get()
    assert stub._flight is telemetry.recorder()
    assert stub.step_wall_series == []
    with pytest.raises(AttributeError, match='_loose'):
        stub.health_stats   # the getter's REAL missing attr is named
    with pytest.raises(AttributeError):
        stub.no_such_attribute


# -- flight recorder -------------------------------------------------------

def test_flight_recorder_ring_bound_and_dump(tmp_path, monkeypatch):
    from autodist_tpu import telemetry
    monkeypatch.setenv('AUTODIST_FLIGHT_RECORDER_EVENTS', '16')
    monkeypatch.setenv('AUTODIST_TELEMETRY_DIR', str(tmp_path))
    telemetry.reset_recorder()
    fr = telemetry.recorder()
    fr.set_context(ns='testns', worker='p0')
    for i in range(100):
        fr.record('step_publish', worker='p0', step=i + 1)
    events = fr.events()
    assert len(events) == 16
    assert events[-1]['step'] == 100 and events[0]['step'] == 85
    assert events[-1]['seq'] == 100   # seq is NOT ring-bounded
    path = fr.dump('unit-test')
    assert path and os.path.dirname(path) == str(tmp_path)
    loaded, meta = telemetry.load_dump(path)
    assert [e['step'] for e in loaded] == \
        [e['step'] for e in events]
    assert meta['reason'] == 'unit-test'
    assert meta['context'] == {'ns': 'testns', 'worker': 'p0'}
    # a second trigger writes its OWN file (first evidence survives)
    path2 = fr.dump('second')
    assert path2 != path and os.path.exists(path)
    assert [r for r, _ in fr.dumps] == ['unit-test', 'second']
    telemetry.reset_recorder()


def test_flight_recorder_dump_never_raises(tmp_path):
    from autodist_tpu.telemetry.flight import FlightRecorder
    fr = FlightRecorder(capacity=16)
    fr.record('x')
    bad = str(tmp_path / 'nodir' / 'deep' / 'f.json')
    # parent dirs missing and not created for an explicit path: the
    # dump degrades to None, never an exception out of a failure path
    assert fr.dump('r', path=bad) is None


# -- trace_view CLI (tier-1 smoke) -----------------------------------------

def test_trace_view_cli_json_smoke(tmp_path):
    records = [
        {'name': 'step', 't0': 5.0, 'dur': 0.01, 'worker': 'p0',
         'tags': {'step': 1, 'worker': 'p0'}},
        {'name': 'step', 't0': 5.02, 'dur': 0.01, 'worker': 'p1',
         'tags': {'step': 1, 'worker': 'p1'}},
    ]
    rec_file = tmp_path / 'records.json'
    rec_file.write_text(json.dumps(records))
    dump_file = tmp_path / 'dump.json'
    dump_file.write_text(json.dumps({
        'reason': 'exclusion:p1', 'context': {'worker': 'p0'},
        'events': [{'seq': 1, 'kind': 'exclude_claim', 'wall': 5.03,
                    'worker': 'p1', 't': 0.0}]}))
    out_file = tmp_path / 'trace.json'
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'trace_view.py'),
         str(rec_file), str(dump_file), '--json', '--out',
         str(out_file)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout)
    assert summary['workers'] == ['p0', 'p1']
    assert summary['span_records'] == 2
    assert summary['flight_events'] == 1
    assert summary['steps'] == {'1': {'p0': 0.01, 'p1': 0.01}}
    trace = json.loads(out_file.read_text())
    assert len(trace['traceEvents']) == summary['trace_events']
    # no-input invocation fails loudly instead of writing an empty trace
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'trace_view.py')],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert bad.returncode == 1


# -- PS-plane aggregation over a real service ------------------------------

@needs_gpp
def test_push_and_collect_records_over_the_wire(service, monkeypatch):
    from autodist_tpu.runtime.coord_client import CoordClient
    from autodist_tpu.telemetry import collect_records, push_records
    # the batch frame must survive a LOSSY session-wide wire setting:
    # aggregate pins wire='f32' explicitly
    monkeypatch.setenv('AUTODIST_PS_WIRE_DTYPE', 'bf16')
    c = CoordClient(('127.0.0.1', service))
    try:
        r0 = [{'name': 'step', 't0': 1.0, 'dur': 0.125,
               'tags': {'step': 1, 'worker': 'p0'}}]
        r1 = [{'name': 'step', 't0': 1.01, 'dur': 0.25,
               'tags': {'step': 1, 'worker': 'p1'}}]
        assert push_records(c, 'ns1', 'p0', r0) > 0
        assert push_records(c, 'ns1', 'p1', r1) > 0
        assert push_records(c, 'ns1', 'p1', []) == 0   # nothing to do
        got = collect_records(c, 'ns1', ['p0', 'p1', 'p9'])
        assert [r['worker'] for r in got] == ['p0', 'p1']
        assert got[0]['dur'] == 0.125 and got[1]['dur'] == 0.25
        # a second batch from the same worker lands as b2
        assert push_records(c, 'ns1', 'p0', r0) > 0
        assert len(collect_records(c, 'ns1', ['p0'])) == 2
    finally:
        c.close()


# -- BSTAT reply format (satellite: documented since PR 9, untested) -------

@needs_gpp
def test_bstat_reply_format_and_vstat(service):
    from autodist_tpu.runtime.coord_client import CoordClient
    c = CoordClient(('127.0.0.1', service))
    try:
        assert c.vstat('ns2/var/none') is None
        assert c._rpc('BSTAT ns2/var/none') == 'NONE'
        c.vset('ns2/var/W', np.zeros(6, np.float32))
        c.vadd('ns2/var/W', np.ones(6, np.float32))
        c.vadd('ns2/var/W', np.ones(6, np.float32))
        # the raw reply format: VAL <pushes> <steps> <elems> <s1> <s2>
        resp = c._rpc('BSTAT ns2/var/W')
        parts = resp.split()
        assert parts[0] == 'VAL' and len(parts) == 6, resp
        pushes, steps, elems, s1, s2 = map(int, parts[1:])
        assert (pushes, steps, elems) == (2, 0, 6)
        assert (s1, s2) == (0, 0)
        stat = c.vstat('ns2/var/W')
        assert stat == {'pushes': 2, 'steps': 0, 'elems': 6,
                        'slot1': False, 'slot2': False}
        # a PS-side optimizer step bumps the shared step index (NOT
        # pushes — BSTEP is an update, not an accumulation) and
        # materializes the momentum slot
        c.vstep('ns2/var/W', np.ones(6, np.float32), 'sgd',
                [0.1, 0.9])
        stat = c.vstat('ns2/var/W')
        assert stat['steps'] == 1 and stat['pushes'] == 2
        assert stat['slot1'] is True
    finally:
        c.close()


# -- per-RPC spans ---------------------------------------------------------

@needs_gpp
def test_coord_client_rpc_spans(service, telem):
    from autodist_tpu.runtime.coord_client import CoordClient
    c = CoordClient(('127.0.0.1', service))
    try:
        c.incr('k', 1)
        c.vset('ns3/var/x', np.ones(4, np.float32))
        recs = telem.get().drain_spans()
        cmds = [r['tags']['cmd'] for r in recs if r['name'] == 'rpc']
        assert 'INCR' in cmds
        batch = [r for r in recs if r['name'] == 'rpc_batch']
        assert batch and batch[0]['tags']['cmd'] == 'BSET'
        assert batch[0]['tags']['bytes'] == 16
    finally:
        c.close()


# -- the chaos acceptance (kill-1 under exclude) ---------------------------

def _ground_truth(W0, feed, steps, lr=0.1):
    W = W0.astype(np.float32).copy()
    denom = np.float32(feed.shape[0] * W0.shape[1])
    for _ in range(steps):
        g = (np.float32(2.0) / denom) * (feed.T @ (feed @ W))
        W = W - np.float32(lr) * g
    return W


@needs_gpp
def test_chaos_exclude_run_produces_conformant_flight_dump(
        service, monkeypatch, tmp_path):
    """ISSUE 11 acceptance: a 2-worker loose-mode run whose peer is
    killed mid-run under policy=exclude (a) keeps training to the
    ground truth, (b) triggers a flight-recorder dump on the
    exclusion, (c) that dump's replayed event trace passes the
    protocol conformance checker, (d) a doctored out-of-order variant
    (epoch bump after floor publish) is rejected with the violated
    invariant named, and (e) the chief's Chrome trace export carries
    both workers' step spans aligned on step ids."""
    import autodist_tpu as ad
    from autodist_tpu import telemetry
    from autodist_tpu.analysis import conformance
    from autodist_tpu.runtime.coord_client import CoordClient
    from autodist_tpu.utils.loose_harness import single_process_loose_env
    monkeypatch.setenv('AUTODIST_PEER_FAILURE_POLICY', 'exclude')
    monkeypatch.setenv('AUTODIST_HEARTBEAT_TIMEOUT', '1.0')
    monkeypatch.setenv('AUTODIST_TELEMETRY', '1')
    monkeypatch.setenv('AUTODIST_TELEMETRY_DIR', str(tmp_path))
    monkeypatch.setenv('AUTODIST_TELEMETRY_PUSH_EVERY', '2')
    telemetry.reset()
    telemetry.reset_recorder()
    steps = 6
    try:
        with single_process_loose_env(service, depth=1):
            autodist = ad.AutoDist(
                resource_info={'nodes': [
                    {'address': 'localhost', 'gpus': [0],
                     'chief': True, 'network_bandwidth': 100}]},
                strategy_builder=ad.strategy.PS(staleness=1))
            rng = np.random.RandomState(0)
            W0 = rng.randn(48, 3).astype(np.float32)
            feed = rng.randn(8, 48).astype(np.float32)
            with autodist.scope():
                x = ad.placeholder(shape=[None, 48],
                                   dtype=np.float32, name='x')
                W = ad.Variable(W0, name='W')
                loss = ad.ops.reduce_mean(
                    ad.ops.square(ad.ops.matmul(x, W)))
                train_op = ad.optimizers.SGD(0.1).minimize(loss, [W])
                autodist._build()   # 2 processes -> loose mode
                ns = autodist._transformed[0].id

                def peer():
                    c = CoordClient(('127.0.0.1', service))
                    try:
                        gen = c.incr('fence/%s/p1' % ns, 0)
                        c.fence('fence/%s/p1' % ns, gen)
                        c.heartbeat('%s/p1' % ns)
                        c.barrier('%s/session/init' % ns, 2,
                                  timeout_s=60.0)
                        batch = []
                        for st in (1, 2):
                            c.heartbeat('%s/p1' % ns)
                            t0 = time.time()
                            c.publish_step('p1', st,
                                           prefix='%s/step/' % ns)
                            batch.append(
                                {'name': 'step', 't0': t0,
                                 'dur': time.time() - t0 + 1e-4,
                                 'tags': {'step': st,
                                          'worker': 'p1'}})
                        telemetry.push_records(c, ns, 'p1', batch)
                        # then dies: no done marker, silence
                    finally:
                        c.close()

                t = threading.Thread(target=peer, daemon=True)
                t.start()
                sess = autodist.create_distributed_session()
                for _ in range(steps):
                    sess.run(train_op, {x: feed})
                w_final = sess.get_variable_value('W')
                t.join(timeout=10.0)
                # (a) the survivor finished on the uninterrupted
                # trajectory (the peer pushed no deltas)
                np.testing.assert_allclose(
                    w_final, _ground_truth(W0, feed, steps),
                    rtol=2e-4, atol=2e-5)
                # uniform per-step wall series covers every train step
                assert len(sess.step_wall_series) == steps
                assert all(w > 0 for w in sess.step_wall_series)
                # (b) the exclusion trigger dumped the ring
                fr = telemetry.recorder()
                dumps = [p for r, p in fr.dumps
                         if r.startswith('exclusion')]
                assert dumps, fr.dumps
                # (e) cohort Chrome trace: both workers, steps aligned
                trace_path = sess.export_chrome_trace(
                    str(tmp_path / 'trace.json'))
                sess.close()
        trace = json.loads(
            (tmp_path / 'trace.json').read_text())
        step_spans = [e for e in trace['traceEvents']
                      if e.get('ph') == 'X' and e['name'] == 'step']
        assert {e['pid'] for e in step_spans} == {0, 1}
        assert all('step' in e['args'] for e in step_spans)
        # (c) the real dump replays clean through the protocol model
        findings, meta = conformance.check_dump(dumps[0])
        assert findings == [], findings
        events, _ = telemetry.load_dump(dumps[0])
        kinds = [e['kind'] for e in events]
        assert 'fence_bump' in kinds and 'exclude_claim' in kinds \
            and 'release' in kinds and 'epoch_bump' in kinds
        # and the --conformance CLI agrees (exit 0)
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, 'tools', 'analyze.py'),
             '--conformance', dumps[0]],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=REPO)
        assert out.returncode == 0, out.stdout + out.stderr
        # (d) a DOCTORED trace — zombie progress after the release —
        # is rejected with the violated invariants named
        doctored = events + [{'seq': 999, 'kind': 'step_publish',
                              'worker': 'p1', 'step': 3}]
        bad = conformance.check_events(doctored)
        assert any('fenced-write-commit' in f for f in bad), bad
        assert any('resurrection' in f for f in bad), bad
    finally:
        telemetry.reset()
        telemetry.reset_recorder()


def test_doctored_admit_inversion_is_rejected():
    """The acceptance's second half, isolated: an admit trace whose
    epoch bump lands AFTER the floor publish (the PR 6 inversion) is
    rejected, and the finding names the violated invariant."""
    from autodist_tpu.analysis import conformance
    clean = [
        {'seq': 1, 'kind': 'admit_claim', 'worker': 'p2', 'world': 3},
        {'seq': 2, 'kind': 'admit_fence_bind', 'worker': 'p2',
         'generation': 0},
        {'seq': 3, 'kind': 'admit_epoch_bump', 'worker': 'p2',
         'epoch': 1},
        {'seq': 4, 'kind': 'admit_floor_publish', 'worker': 'p2',
         'floor': 2},
    ]
    assert conformance.check_events(clean) == []
    doctored = [clean[0], clean[1], clean[3], clean[2]]
    findings = conformance.check_events(doctored)
    assert len(findings) == 1
    assert 'admit-inversion' in findings[0]
    assert 'no invisible frozen counter' in findings[0]
    assert 'PR6_ADMIT_INVERSION' in findings[0]
