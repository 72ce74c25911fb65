"""Liveness edges + generation fencing (ISSUE 4).

Satellite coverage: `dead_workers`/`_check_peers_alive` distinguishing
a cleanly-closed session (stops beating, NOT dead) from a crash, a
never-seen beat counter reading as dead after the window, and the
staleness-gate fail-fast firing within the timeout. Tentpole coverage:
the FENCE protocol end-to-end at the client/service level.

Tier-1 safe on CPU (skipped without g++, like test_native.py)."""
import shutil
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(shutil.which('g++') is None,
                                reason='g++ unavailable')


# -- dead_workers edges ------------------------------------------------------

def test_dead_workers_requires_window_on_own_clock(coord):
    """A beating worker is never dead; one that stops beating is dead
    only after the timeout has elapsed on the OBSERVER's clock."""
    c = coord()
    obs = {}
    c.heartbeat('lv/a')
    t0 = 1000.0
    assert c.dead_workers(['lv/a'], 5.0, obs, now=t0) == []
    # still within the window: not dead
    assert c.dead_workers(['lv/a'], 5.0, obs, now=t0 + 4.0) == []
    # beat advances -> window restarts
    c.heartbeat('lv/a')
    assert c.dead_workers(['lv/a'], 5.0, obs, now=t0 + 6.0) == []
    assert c.dead_workers(['lv/a'], 5.0, obs,
                          now=t0 + 11.5) == ['lv/a']


def test_never_beat_reads_as_dead_after_window(coord):
    """A worker whose beat counter NEVER advanced (it died before its
    first heartbeat, or its key was purged) is declared dead once the
    window elapses — a missing timestamp must not read as immortal."""
    c = coord()
    obs = {}
    t0 = 2000.0
    assert c.dead_workers(['lv/ghost'], 3.0, obs, now=t0) == []
    assert c.dead_workers(['lv/ghost'], 3.0, obs,
                          now=t0 + 3.5) == ['lv/ghost']


def test_clean_close_is_not_a_crash(coord, monkeypatch):
    """_check_peers_alive: a peer that published its done marker (clean
    Session.close) stops beating WITHOUT being declared dead; a peer
    with no marker raises. Exercised on the real session method with a
    minimal stub session (the full-stack version lives in
    tests/integration/test_multiprocess.py)."""
    from autodist_tpu.runtime.session import Session
    c = coord()
    c.heartbeat('ns1/p1')
    c.heartbeat('ns1/p2')

    sess = Session.__new__(Session)
    sess._coord = c
    sess._ns = 'ns1'
    sess._worker_name = 'p0'
    sess._num_workers = 3
    sess._hb_peers = ['ns1/p1', 'ns1/p2']
    sess._hb_seen = {}
    sess._excluded = set()
    sess._dead_since = {}
    sess._epoch_seen = 0
    sess._policy = 'fail'
    sess._min_workers = 1
    sess._health = {'missed_beats': 0, 'epoch_bumps': 0,
                    'exclusions': [], 'rejoins': [],
                    'recovery_wall_s': []}
    monkeypatch.setenv('AUTODIST_HEARTBEAT_TIMEOUT', '0.2')

    sess._check_peers_alive()          # baseline observations
    time.sleep(0.35)                   # both peers go silent
    c.set('done/ns1/p1', '1')          # p1 closed cleanly
    with pytest.raises(RuntimeError, match='missed heartbeats') as ei:
        sess._check_peers_alive()
    assert 'p2' in str(ei.value) and 'p1' not in str(ei.value)


def test_gate_fail_fast_fires_within_timeout(coord):
    """A failure_check raising surfaces from the staleness gate within
    its slice, far before the full gate window."""
    c = coord()
    c.publish_step('p0', 5, prefix='gate1/step/')

    def boom():
        raise RuntimeError('peer dead (injected)')

    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match='peer dead'):
        c.staleness_gate(5, 1, 2, timeout_s=60.0,
                         prefix='gate1/step/', failure_check=boom,
                         slice_s=0.2)
    assert time.monotonic() - t0 < 5.0


def test_gate_membership_is_reevaluated_per_slice(coord):
    """The gate re-reads a CALLABLE membership every slice: shrinking
    the quorum (policy=exclude deleting the dead worker's step key)
    releases a blocked waiter instead of timing it out."""
    c = coord()
    parties = {'n': 2}
    c.publish_step('p0', 5, prefix='gate2/step/')
    c.publish_step('p1', 1, prefix='gate2/step/')   # laggard

    calls = {'n': 0}

    def shrink_after_two_slices():
        calls['n'] += 1
        if calls['n'] == 2:
            # the "excluder": drop the laggard and shrink the quorum
            c.delete('gate2/step/p1')
            parties['n'] = 1

    t0 = time.monotonic()
    c.staleness_gate(5, 1, lambda: parties['n'], timeout_s=30.0,
                     prefix='gate2/step/',
                     failure_check=shrink_after_two_slices,
                     slice_s=0.2)
    assert time.monotonic() - t0 < 10.0
    assert calls['n'] >= 2


def test_gate_party_count_reevaluates_upward_mid_run(coord):
    """ISSUE 6: the gate re-reads its CALLABLE membership every slice
    in BOTH directions — a slice that starts with 2 parties completes
    with 3. A worker admitted mid-wait (its step key published before
    the party count grew, per the admit-handshake ordering) becomes a
    party the gate genuinely waits for: after the growth the gate must
    NOT release until the third party reaches the bound."""
    c = coord()
    parties = {'n': 2}
    c.publish_step('p0', 5, prefix='gate4/step/')
    c.publish_step('p1', 1, prefix='gate4/step/')   # laggard

    calls = {'n': 0}

    def grow_then_release():
        calls['n'] += 1
        if calls['n'] == 2:
            # the joiner: publishes its adopted floor FIRST, then
            # membership grows (admit_worker's ordering); the laggard
            # then catches up, so only the NEW party still binds
            c.publish_step('p2', 1, prefix='gate4/step/')
            parties['n'] = 3
            c.publish_step('p1', 5, prefix='gate4/step/')
        if calls['n'] == 4:
            c.publish_step('p2', 5, prefix='gate4/step/')

    t0 = time.monotonic()
    c.staleness_gate(5, 1, lambda: parties['n'], timeout_s=30.0,
                     prefix='gate4/step/',
                     failure_check=grow_then_release, slice_s=0.2)
    assert time.monotonic() - t0 < 10.0
    # the gate kept waiting after the growth: it only released once
    # the THIRD party published past the bound (call 4), proving the
    # upward re-evaluation actually bound it
    assert calls['n'] >= 4


def test_session_membership_grows_on_epoch_bump(coord, monkeypatch):
    """_check_peers_alive adopts a live JOIN: the epoch bump published
    by an admitted worker (runtime.session.admit_worker) grows the
    session's world, its gate party count and its heartbeat peer list
    — even with heartbeats DISABLED, because membership growth is not
    failure detection."""
    from autodist_tpu.runtime.session import Session, admit_worker
    c = coord()
    ns = 'nsg'
    c.set(ns + '/session/init-done', '1')
    c.incr(ns + '/join/world', 2)
    c.publish_step('p0', 3, prefix=ns + '/step/')
    c.publish_step('p1', 3, prefix=ns + '/step/')

    sess = Session.__new__(Session)
    sess._coord = c
    sess._ns = ns
    sess._worker_name = 'p0'
    sess._num_workers = 2
    sess._world = 2
    sess._hb_peers = [ns + '/p1']
    sess._hb_seen = {}
    sess._excluded = set()
    sess._dead_since = {}
    sess._epoch_seen = 0
    sess._policy = 'fail'
    sess._min_workers = 1
    sess._is_chief = False
    sess._health = {'missed_beats': 0, 'epoch_bumps': 0,
                    'exclusions': [], 'rejoins': [],
                    'recovery_wall_s': [], 'joins': [], 'replans': []}
    monkeypatch.setenv('AUTODIST_HEARTBEAT_TIMEOUT', '0')
    monkeypatch.setenv('AUTODIST_PROCESS_ID', '0')

    assert sess._active_workers() == 2
    admit = admit_worker(coord(), ns)
    assert admit['worker'] == 'p2' and admit['epoch'] == 1
    sess._check_peers_alive()
    assert sess._world == 3 and sess._active_workers() == 3
    assert ns + '/p2' in sess._hb_peers
    assert sess._health['joins'] == [{'worker': 'p2', 'epoch': 1}]
    assert sess._live_members() == [0, 1, 2]


def test_gate_rearms_while_restart_pending(coord):
    """A truthy failure_check (policy=restart: recovery in flight)
    re-arms the gate window: a respawn + recompile longer than one
    window must not TimeoutError while the supervisor is still working
    — the runbook's no-timeout-while-restarts-remain contract."""
    c = coord()
    c.publish_step('p0', 5, prefix='gate3/step/')

    def replacement_rejoins_late():
        # laggard's reborn incarnation publishes after ~3 windows
        time.sleep(1.3)
        coord().publish_step('p1', 5, prefix='gate3/step/')

    t = threading.Thread(target=replacement_rejoins_late, daemon=True)
    t.start()
    t0 = time.monotonic()
    c.staleness_gate(5, 1, 2, timeout_s=0.5, prefix='gate3/step/',
                     failure_check=lambda: True, slice_s=0.1)
    elapsed = time.monotonic() - t0
    t.join(10.0)
    assert elapsed > 1.0      # waited well past the 0.5s window


def test_restart_wait_cap_bounds_a_silent_supervisor(coord,
                                                     monkeypatch):
    """policy=restart: a peer dead past AUTODIST_RESTART_WAIT_S with
    neither a replacement heartbeat nor a failed marker raises instead
    of re-arming the gate forever (the supervisor itself died)."""
    from autodist_tpu.runtime.session import Session
    c = coord()
    c.heartbeat('ns2/p1')

    sess = Session.__new__(Session)
    sess._coord = c
    sess._ns = 'ns2'
    sess._worker_name = 'p0'
    sess._num_workers = 2
    sess._hb_peers = ['ns2/p1']
    sess._hb_seen = {}
    sess._excluded = set()
    sess._dead_since = {}
    sess._epoch_seen = 0
    sess._policy = 'restart'
    sess._min_workers = 1
    sess._health = {'missed_beats': 0, 'epoch_bumps': 0,
                    'exclusions': [], 'rejoins': [],
                    'recovery_wall_s': []}
    monkeypatch.setenv('AUTODIST_HEARTBEAT_TIMEOUT', '0.2')
    monkeypatch.setenv('AUTODIST_RESTART_WAIT_S', '0.3')

    sess._check_peers_alive()          # baseline observations
    time.sleep(0.35)                   # p1 goes silent
    # recovery pending: truthy (gate re-arms), death time recorded
    assert sess._check_peers_alive() is True
    assert 'ns2/p1' in sess._dead_since
    time.sleep(0.45)                   # past the wait cap, no rebirth
    with pytest.raises(RuntimeError, match='no supervised replacement'):
        sess._check_peers_alive()


# -- generation fencing ------------------------------------------------------

def test_fence_rejects_superseded_writer_everywhere(coord):
    """After the fence counter advances, EVERY write on the old
    generation's connection is rejected typed — KV set, counter incr
    (publish_step), tensor set/add/step — while reads stay open."""
    from autodist_tpu.runtime.coord_client import FencedWriteError
    zombie = coord()
    zombie.fence('fz/fence/p1', 0)
    zombie.vset('fz/var/w', np.ones(4, np.float32))
    zombie.publish_step('p1', 2, prefix='fz/step/')

    survivor = coord()
    survivor.incr('fz/fence/p1', 1)    # declare p1 dead

    with pytest.raises(FencedWriteError):
        zombie.publish_step('p1', 3, prefix='fz/step/')
    with pytest.raises(FencedWriteError):
        zombie.vadd('fz/var/w', np.ones(4, np.float32))
    with pytest.raises(FencedWriteError):
        zombie.vset('fz/var/w', np.zeros(4, np.float32))
    with pytest.raises(FencedWriteError):
        zombie.set('fz/kv', 'x')
    with pytest.raises(FencedWriteError):
        zombie.vstep('fz/var/w', np.ones(4, np.float32), 'sgd',
                     [0.1, 0.0])
    # deletes are mutations too: a fenced zombie reaching a cleanup
    # path (e.g. close()'s purge) must not erase live run state
    with pytest.raises(FencedWriteError):
        zombie.delete('fz/kv2')
    with pytest.raises(FencedWriteError):
        zombie.delete_namespace('fz/')
    # reads are harmless and stay open on the fenced connection
    assert zombie.incr('fz/step/p1', 0) == 2
    np.testing.assert_array_equal(zombie.vget('fz/var/w', shape=(4,)),
                                  np.ones(4, np.float32))
    # nothing the zombie attempted after the fence landed
    np.testing.assert_array_equal(survivor.vget('fz/var/w', shape=(4,)),
                                  np.ones(4, np.float32))


def test_replacement_joins_under_fresh_generation(coord):
    """The reborn worker reads the bumped counter and fences with the
    NEW generation: its writes land; binding with the stale generation
    is rejected at FENCE time."""
    from autodist_tpu.runtime.coord_client import FencedWriteError
    survivor = coord()
    survivor.incr('fr/fence/p1', 1)
    stale = coord()
    with pytest.raises(FencedWriteError):
        stale.fence('fr/fence/p1', 0)
    reborn = coord()
    gen = reborn.incr('fr/fence/p1', 0)
    assert gen == 1
    reborn.fence('fr/fence/p1', gen)
    reborn.vadd('fr/var/w', np.full(3, 2.0, np.float32))
    np.testing.assert_array_equal(
        survivor.vget('fr/var/w', shape=(3,)),
        np.full(3, 2.0, np.float32))


def test_fenced_chunked_write_aborts_open_sequence(coord, monkeypatch):
    """A writer fenced BETWEEN chunks of one logical push aborts its
    open sequence server-side: readers are not wedged on a permanently
    odd version (the torn-read parity bit is released)."""
    from autodist_tpu.runtime.coord_client import (CoordClient,
                                                   FencedWriteError)
    monkeypatch.setattr(CoordClient, 'STALL_TIMEOUT_S', 1.0)
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '20')  # 5 f32/chunk
    writer = coord()
    writer.fence('fc/fence/p1', 0)
    survivor = coord()
    val = np.arange(10, dtype=np.float32)
    writer.vset('fc/var/w', val)       # seeds (2 chunks, completes)

    # fence lands between the chunks of the writer's NEXT push
    real_send = CoordClient._send_frame
    fired = []

    def fence_between_chunks(self, line, payload=None):
        if self is writer and line.startswith('BSET fc/var/w') \
                and ' 5 10' in line and not fired:
            fired.append(True)
            survivor.incr('fc/fence/p1', 1)
        return real_send(self, line, payload)

    monkeypatch.setattr(CoordClient, '_send_frame',
                        fence_between_chunks)
    with pytest.raises(FencedWriteError):
        writer.vset('fc/var/w', val * 3)
    assert fired
    # the aborted sequence released the parity bit: a read succeeds
    # (first chunk of the rejected push may or may not have landed
    # before the fence; whole-chunk granularity either way)
    got = survivor.vget('fc/var/w', shape=(10,))
    assert got is not None and got.shape == (10,)


def test_health_report_shapes(coord):
    """profiling.health_report/format_health over session-shaped stats
    plus faultline events."""
    from autodist_tpu.utils.faultline import FaultLine, FaultPlan
    from autodist_tpu.utils.profiling import format_health, health_report
    assert health_report({}) == {}
    assert '(no loose-mode session' in format_health({})
    fl = FaultLine(FaultPlan([{'kind': 'drop_conn', 'match': 'BADD'}]))
    fl.events.append({'kind': 'drop_conn', 'fault': {}, 'line': 'BADD x',
                      'time': 0.0})
    stats = {'policy': 'exclude', 'generation': 0, 'epoch': 1,
             'epoch_bumps': 1, 'num_workers': 4, 'active_workers': 3,
             'missed_beats': 1,
             'exclusions': [{'worker': 'p3', 'epoch': 1}],
             'rejoins': ['p2'], 'recovery_wall_s': [2.5],
             'auto_checkpoints': 2}
    rep = health_report(stats, faultline=fl)
    assert rep['policy'] == 'exclude'
    assert rep['active_workers'] == 3 and rep['num_workers'] == 4
    assert rep['exclusions'] == [{'worker': 'p3', 'epoch': 1}]
    assert rep['restarts_observed'] == 1
    assert rep['max_recovery_wall_s'] == 2.5
    assert rep['injected_faults'] == [{'kind': 'drop_conn',
                                       'line': 'BADD x'}]
    txt = format_health(rep)
    assert 'excluded p3' in txt and 'p2 rejoined' in txt
    assert 'injected: drop_conn' in txt
