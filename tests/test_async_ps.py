"""Async PS data plane (ISSUE 3): pipelined multi-tensor RPCs
(vmget/vmset/vmadd), the persistent TransferPool, and the loose-mode
session pipeline (AUTODIST_PS_PIPELINE_DEPTH) — push->publish ordering,
read-your-writes, and depth-1 bit-exactness with the serial plane.

Tier-1 safe on CPU: everything runs single-process against a live
coord_service on a private port (skipped without g++, like
test_native.py).
"""
import select
import shutil
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

HAVE_GXX = shutil.which('g++') is not None

pytestmark = pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')


# -- pipelined multi-tensor RPCs ----------------------------------------------

@pytest.mark.parametrize('wire', ['f32', 'bf16'])
def test_vmset_vmget_multi_key_multi_chunk_exact(coord, monkeypatch,
                                                 wire):
    """vmset/vmget move several tensors per wire round trip with vset/
    vget's exact chunking: values survive bit-for-bit (f32) or at bf16
    rounding, across uneven tail chunks and both wire dtypes."""
    import ml_dtypes
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '4096')  # force chunks
    c = coord()
    rng = np.random.RandomState(3)
    tensors = {'mk/a': rng.randn(5000).astype(np.float32),   # 5 chunks
               'mk/b': rng.randn(100, 7).astype(np.float32),
               'mk/c': rng.randn(3).astype(np.float32)}      # 1 frame
    c.vmset(sorted(tensors.items()), wire=wire)
    specs = [(k, v.shape) for k, v in sorted(tensors.items())]
    got = c.vmget(specs, wire=wire)
    for (k, _), arr in zip(specs, got):
        want = tensors[k]
        if wire == 'bf16':
            want = want.astype(ml_dtypes.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(arr, want, err_msg=k)
    # absent keys come back None WITHOUT disturbing the others
    got = c.vmget([('mk/a', (5000,)), ('mk/none', (4,)),
                   ('mk/c', (3,))])
    assert got[1] is None
    assert got[0].shape == (5000,) and got[2].shape == (3,)


def test_vmadd_accumulates_and_counts(coord, monkeypatch):
    """vmadd: one pipelined batch accumulates exactly and returns
    per-key push counts; a chunked delta counts ONE push."""
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '4096')
    c = coord()
    rng = np.random.RandomState(4)
    a = rng.randn(5000).astype(np.float32)
    b = rng.randn(16).astype(np.float32)
    c.vmset([('ma/a', a), ('ma/b', b)])
    counts = c.vmadd([('ma/a', a), ('ma/b', b)])
    assert counts == {'ma/a': 1, 'ma/b': 1}
    assert c.vmadd([('ma/b', b)])['ma/b'] == 2
    np.testing.assert_allclose(c.vget('ma/a', shape=(5000,)), 2 * a,
                               rtol=1e-6)
    np.testing.assert_allclose(c.vget('ma/b', shape=(16,)), 3 * b,
                               rtol=1e-6)


def test_vmget_torn_read_interleaving(coord, monkeypatch):
    """A chunked write in flight on ONE key stalls only that key: the
    batched pull retries it (raising the mid-flight error if the
    writer stays stuck) while clean keys assemble exactly."""
    from autodist_tpu.runtime.coord_client import CoordClient
    monkeypatch.setattr(CoordClient, 'STALL_TIMEOUT_S', 0.3)
    monkeypatch.setenv('AUTODIST_PS_TORN_RETRIES', '5')
    c = coord()
    w = coord()
    t = np.arange(10, dtype=np.float32)
    clean = np.full(6, 7.0, np.float32)
    c.vmset([('torn/seq', t), ('torn/clean', clean)])
    # writer opens a 2-chunk reset and stalls mid-flight
    half = t[:5].tobytes()
    assert w._rpc('BSET torn/seq %d f32 0 10' % len(half), half) == 'OK'
    with pytest.raises(OSError, match='mid-flight'):
        c.vmget([('torn/seq', (10,)), ('torn/clean', (6,))])
    # the writer completes -> the same batched pull succeeds
    assert w._rpc('BSET torn/seq %d f32 5 10' % len(half),
                  t[5:].tobytes()) == 'OK'
    got = c.vmget([('torn/seq', (10,)), ('torn/clean', (6,))])
    np.testing.assert_array_equal(got[0], t)
    np.testing.assert_array_equal(got[1], clean)


def test_vmget_retries_version_skew_between_chunks(coord, monkeypatch):
    """A whole push landing between one key's pipelined chunks (even
    parity, version moved) forces a retry of that key; the retry with a
    quiesced writer returns a consistent assembly — no half-applied
    mix."""
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '20')  # 5 f32 / chunk
    c = coord()
    pusher = coord()
    base = np.arange(10, dtype=np.float32)
    c.vset('skew/k', base)
    from autodist_tpu.runtime.coord_client import CoordClient
    real_send = CoordClient._send_frame
    seen = []

    def send_with_one_push(self, line, payload=None):
        # one whole push lands between the FIRST attempt's two chunks:
        # requests are pipelined, so chunk two's send says nothing of
        # chunk one. Its reply on the socket does: the service has read
        # chunk one, at the old version, and has not seen chunk two.
        if self is c and line.startswith('BGET skew/k'):
            seen.append(line)
            if len(seen) == 2:
                assert select.select([c._sock], [], [], 60.0)[0]
                pusher.vadd('skew/k', np.ones(10, np.float32))
        return real_send(self, line, payload)

    monkeypatch.setattr(CoordClient, '_send_frame', send_with_one_push)
    got = c.vget('skew/k', shape=(10,))
    np.testing.assert_array_equal(got, base + 1.0)
    assert len(seen) > 2   # first attempt torn -> at least one retry


def test_stall_timeout_env_knob(coord, monkeypatch):
    """AUTODIST_PS_STALL_TIMEOUT_S overrides the stall window, and is
    validated in const.py like the sibling PS knobs."""
    from autodist_tpu.const import ENV
    from autodist_tpu.runtime.coord_client import CoordClient
    monkeypatch.setenv('AUTODIST_PS_STALL_TIMEOUT_S', '0.2')
    assert ENV.AUTODIST_PS_STALL_TIMEOUT_S.val == 0.2
    c = coord()
    assert c.stall_timeout_s == 0.2
    monkeypatch.setenv('AUTODIST_PS_STALL_TIMEOUT_S', '-1')
    with pytest.raises(ValueError, match='AUTODIST_PS_STALL_TIMEOUT_S'):
        ENV.AUTODIST_PS_STALL_TIMEOUT_S.val
    monkeypatch.delenv('AUTODIST_PS_STALL_TIMEOUT_S')
    assert c.stall_timeout_s == CoordClient.STALL_TIMEOUT_S
    # the knob is live: a writer stuck mid-flight surfaces within the
    # configured window instead of the 10 s default
    t = np.arange(10, dtype=np.float32)
    c.vset('stall/knob', t)
    w = coord()
    half = t[:5].tobytes()
    assert w._rpc('BSET stall/knob %d f32 0 10' % len(half),
                  half) == 'OK'
    monkeypatch.setenv('AUTODIST_PS_STALL_TIMEOUT_S', '0.2')
    t0 = time.monotonic()
    with pytest.raises(OSError, match='mid-flight'):
        c.vget('stall/knob', shape=(10,))
    assert time.monotonic() - t0 < 5.0
    assert w._rpc('BSET stall/knob %d f32 5 10' % len(half),
                  t[5:].tobytes()) == 'OK'


def test_pipeline_depth_env_validated(monkeypatch):
    from autodist_tpu.const import ENV
    assert ENV.AUTODIST_PS_PIPELINE_DEPTH.val == 1
    monkeypatch.setenv('AUTODIST_PS_PIPELINE_DEPTH', '2')
    assert ENV.AUTODIST_PS_PIPELINE_DEPTH.val == 2
    monkeypatch.setenv('AUTODIST_PS_PIPELINE_DEPTH', '0')
    with pytest.raises(ValueError, match='AUTODIST_PS_PIPELINE_DEPTH'):
        ENV.AUTODIST_PS_PIPELINE_DEPTH.val


def test_encode_skips_copy_on_conforming_input():
    """The f32 wire path is zero-copy for contiguous float32 input (the
    session hot path); non-conforming inputs still convert exactly."""
    from autodist_tpu.runtime.coord_client import _as_f32_flat, _encode
    a = np.arange(12, dtype=np.float32)
    flat = _as_f32_flat(a)
    assert flat.base is a or flat is a          # view, not a copy
    payload = _encode(a, 'f32')
    assert isinstance(payload, memoryview)
    assert len(payload) == a.nbytes
    assert bytes(payload) == a.tobytes()
    b = np.arange(12, dtype=np.float64).reshape(3, 4).T
    assert bytes(_encode(b, 'f32')) == \
        np.ascontiguousarray(b.astype(np.float32)).tobytes()


# -- TransferPool -------------------------------------------------------------

class _FakeClient:
    def close(self):
        pass


def test_transfer_pool_fifo_and_concurrency():
    """Jobs on ONE endpoint run in submission order (the read-your-
    writes backbone); distinct endpoints run concurrently."""
    from autodist_tpu.runtime.coord_client import TransferPool
    order = []
    gate = threading.Event()
    pool = TransferPool([_FakeClient, _FakeClient])
    try:
        def slow(_):
            gate.wait(5.0)
            order.append('ep0-slow')

        def after(_):
            order.append('ep0-after')

        def other(_):
            order.append('ep1')
            gate.set()

        jobs = [pool.submit(0, slow), pool.submit(0, after),
                pool.submit(1, other)]
        for j in jobs:
            j.result(timeout=10.0)
        assert order == ['ep1', 'ep0-slow', 'ep0-after']
    finally:
        pool.close()


def test_transfer_pool_submit_after_close_raises():
    """A submit after close() must raise, not enqueue a job no worker
    will ever run (whose joiner would hang forever)."""
    from autodist_tpu.runtime.coord_client import TransferPool
    pool = TransferPool([_FakeClient])
    assert pool.run([(0, lambda _: 'ok')]) == ['ok']
    pool.close()
    with pytest.raises(OSError, match='closed'):
        pool.submit(0, lambda _: 'never')


def test_transfer_pool_aggregates_endpoint_errors():
    """ISSUE 3 satellite: one failing endpoint re-raises as itself
    (type-preserving); several raise ONE aggregate naming every
    endpoint — no endpoint's error is silently dropped."""
    from autodist_tpu.runtime.coord_client import TransferPool
    pool = TransferPool([_FakeClient] * 3)
    try:
        def boom(tag):
            def go(_):
                raise ValueError('endpoint %s wire down' % tag)
            return go

        def ok(_):
            return 'fine'

        with pytest.raises(ValueError, match='wire down'):
            pool.run([(0, boom('A')), (1, ok), (2, ok)])
        with pytest.raises(RuntimeError) as ei:
            pool.run([(0, boom('A')), (1, ok), (2, boom('C'))])
        msg = str(ei.value)
        assert 'endpoint 0' in msg and 'endpoint 2' in msg
        assert 'A wire down' in msg and 'C wire down' in msg
        # the pool stays usable after failures
        assert pool.run([(1, ok)]) == ['fine']
    finally:
        pool.close()


def test_transfer_pool_reconnects_after_connection_error(coord):
    """A dead connection fails its job but the worker redials on the
    next one instead of wedging the endpoint."""
    from autodist_tpu.runtime.coord_client import TransferPool
    pool = TransferPool([lambda: coord()])
    try:
        pool.run([(0, lambda c: c.set('pool/alive', '1'))])

        def kill(c):
            c._sock.close()
            return c.get('pool/alive')   # OSError on the dead socket

        with pytest.raises(OSError):
            pool.run([(0, kill)])
        assert pool.run([(0, lambda c: c.get('pool/alive'))]) == ['1']
    finally:
        pool.close()


# -- loose-mode session pipeline ----------------------------------------------

@contextmanager
def _loose_session(monkeypatch, coord_port, depth, staleness=2,
                   dim=48, seed=0):
    """Single-process loose-mode session harness: the build-sees-2/
    session-sees-1 env dance lives in
    ``utils.loose_harness.single_process_loose_env``. Yields
    (sess, train_op, x placeholder, W0, feed)."""
    del monkeypatch   # env handled (and restored) by the shared harness
    import autodist_tpu as ad
    from autodist_tpu.utils.loose_harness import single_process_loose_env
    with single_process_loose_env(coord_port, depth) as session_sees_one:
        autodist = ad.AutoDist(
            resource_info={'nodes': [
                {'address': 'localhost', 'gpus': [0], 'chief': True,
                 'network_bandwidth': 100}]},
            strategy_builder=ad.strategy.PS(staleness=staleness))
        rng = np.random.RandomState(seed)
        W0 = rng.randn(dim, 3).astype(np.float32)
        feed = rng.randn(8, dim).astype(np.float32)
        with autodist.scope():
            x = ad.placeholder(shape=[None, dim], dtype=np.float32,
                               name='x')
            W = ad.Variable(W0, name='W')
            loss = ad.ops.reduce_mean(
                ad.ops.square(ad.ops.matmul(x, W)))
            train_op = ad.optimizers.SGD(0.1).minimize(loss, [W])
            autodist._build()   # sees 2 processes -> loose mode
            session_sees_one()
            sess = autodist.create_distributed_session()
            assert sess._loose, 'harness must land in loose mode'
            assert sess._pipeline_depth == min(depth, 2)
            try:
                yield sess, train_op, x, W0, feed
            finally:
                sess.close()


def _serial_ground_truth(W0, feed, steps, lr=0.1):
    """The serial loose-mode data plane in numpy: pull -> local SGD
    step -> delta push, one worker. grad of mean((xW)^2) wrt W is
    2/(n*m) * x^T (x W)."""
    W = W0.astype(np.float32).copy()
    denom = np.float32(feed.shape[0] * W0.shape[1])
    for _ in range(steps):
        g = (np.float32(2.0) / denom) * (feed.T @ (feed @ W))
        W = W - np.float32(lr) * g
    return W


@pytest.mark.parametrize('depth', [1, 2])
def test_loose_session_matches_serial_ground_truth(coord, monkeypatch,
                                                   depth):
    """Depth 1 IS the serial plane; depth 2 must not change one
    worker's math (the pull-ahead happens strictly after the push —
    read-your-writes). Both track the analytic serial trajectory."""
    host, port = coord().address
    with _loose_session(monkeypatch, port, depth) as (
            sess, train_op, x, W0, feed):
        for _ in range(5):
            sess.run(train_op, {x: feed})
        got = sess.get_variable_value('W')
        stats = sess.ps_stats
    want = _serial_ground_truth(W0, feed, 5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    pipe = stats['pipeline']
    assert pipe['depth'] == depth
    assert pipe['train_steps'] == 5
    assert pipe['pull_s'] > 0 and pipe['push_s'] > 0
    if depth == 1:
        assert pipe['overlap_frac'] == 0.0


def test_loose_session_depth2_bit_identical_to_depth1(coord,
                                                      monkeypatch):
    """ISSUE 3 acceptance: the pipelined plane is a pure latency
    optimization — a single worker's final variable state at depth 2
    is BIT-identical to depth 1 (same pulls, same deltas, same
    order)."""
    host, port = coord().address
    finals = {}
    for depth in (1, 2):
        with _loose_session(monkeypatch, port, depth, seed=7) as (
                sess, train_op, x, W0, feed):
            for _ in range(6):
                sess.run(train_op, {x: feed})
            finals[depth] = sess.get_variable_value('W')
    np.testing.assert_array_equal(finals[1], finals[2])


def test_depth2_push_precedes_publish_and_next_pull(coord, monkeypatch):
    """The ordering invariants the staleness gate and read-your-writes
    depend on, observed at the client surface: for every step N, the
    delta push (vmadd) completes before N's publish_step, and the
    pull-ahead (vmget) only issues after both. One worker + one
    pipeline thread make the event order deterministic."""
    from autodist_tpu.runtime.coord_client import CoordClient
    events = []
    lock = threading.Lock()
    real_vmadd = CoordClient.vmadd
    real_vmget = CoordClient.vmget
    real_publish = CoordClient.publish_step

    def log(tag):
        with lock:
            events.append(tag)

    def vmadd_logged(self, items, wire=None):
        out = real_vmadd(self, items, wire=wire)
        log('push')
        return out

    def vmget_logged(self, specs, dtype=np.float32, wire=None):
        log('pull')
        return real_vmget(self, specs, dtype=dtype, wire=wire)

    def publish_logged(self, worker, step, prefix='step/'):
        log('publish')
        return real_publish(self, worker, step, prefix=prefix)

    monkeypatch.setattr(CoordClient, 'vmadd', vmadd_logged)
    monkeypatch.setattr(CoordClient, 'vmget', vmget_logged)
    monkeypatch.setattr(CoordClient, 'publish_step', publish_logged)
    host, port = coord().address
    steps = 3
    with _loose_session(monkeypatch, port, 2) as (
            sess, train_op, x, W0, feed):
        for _ in range(steps):
            sess.run(train_op, {x: feed})
    # step N: push, publish, pull-ahead(N+1); close drains the last
    # job then publishes the release sentinel
    expected = ['pull'] + ['push', 'publish', 'pull'] * steps + \
        ['publish']
    assert events == expected


def test_depth2_records_overlap(coord, monkeypatch):
    """With a host tail between steps that outlasts the background
    push, depth 2 hides wire time: the session's measured overlap_frac
    is > 0 and the profiling report attributes hidden vs exposed wire
    seconds."""
    from autodist_tpu.utils.profiling import (format_ps_overlap,
                                              ps_overlap_report)
    host, port = coord().address
    with _loose_session(monkeypatch, port, 2, dim=256) as (
            sess, train_op, x, W0, feed):
        sess.run(train_op, {x: feed})          # compile + warmup
        for _ in range(4):
            # the input pipeline's interval: as long as the push and
            # the pull-ahead take, whatever else the machine is doing
            sess._inflight.result(timeout=60.0)
            sess.run(train_op, {x: feed})
        sess.get_variable_value('W')           # drain the last push
        stats = sess.ps_stats
    rep = ps_overlap_report(stats)
    assert rep['depth'] == 2 and rep['train_steps'] == 5
    assert rep['overlap_frac'] > 0.0
    assert rep['hidden_wire_s'] > 0.0
    assert rep['wire_s'] >= rep['exposed_wire_s']
    assert 'overlap' in format_ps_overlap(rep)


def test_depth2_background_push_error_surfaces(coord, monkeypatch):
    """A failed background push re-raises on the next run() instead of
    being silently lost."""
    from autodist_tpu.runtime import session as session_mod
    host, port = coord().address
    with _loose_session(monkeypatch, port, 2) as (
            sess, train_op, x, W0, feed):
        sess.run(train_op, {x: feed})
        sess.get_variable_value('W')           # drain step 1 cleanly
        real = session_mod.Session._push_ps_deltas

        def boom(self, pulled, shared_push=None, scale=1.0):
            raise OSError('injected push failure')

        monkeypatch.setattr(session_mod.Session, '_push_ps_deltas',
                            boom)
        sess.run(train_op, {x: feed})          # queues the failing push
        with pytest.raises(OSError, match='injected push failure'):
            sess.run(train_op, {x: feed})
        monkeypatch.setattr(session_mod.Session, '_push_ps_deltas',
                            real)


def test_get_variable_value_drains_pipeline(coord, monkeypatch):
    """Read-your-writes at the API surface: an authoritative read right
    after run() reflects the just-pushed update even at depth 2."""
    host, port = coord().address
    with _loose_session(monkeypatch, port, 2, seed=11) as (
            sess, train_op, x, W0, feed):
        sess.run(train_op, {x: feed})
        w1 = sess.get_variable_value('W')
        assert np.abs(w1 - W0).max() > 1e-7    # the push landed
        np.testing.assert_allclose(
            w1, _serial_ground_truth(W0, feed, 1), rtol=2e-4,
            atol=2e-5)
        # a read pushes nothing, so it must KEEP the prefetched pull
        # for the next run() instead of degrading depth 2 to a serial
        # refetch — and the next step still matches ground truth
        assert sess._stashed_prefetch is not None
        sess.run(train_op, {x: feed})
        np.testing.assert_allclose(
            sess.get_variable_value('W'),
            _serial_ground_truth(W0, feed, 2), rtol=2e-4, atol=2e-5)
