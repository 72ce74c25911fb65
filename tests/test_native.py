"""Native (C++) runtime components: coordination service + data loader.

These build from source on first use (g++); tests skip gracefully where
no toolchain exists.
"""
import os
import shutil
import threading

import numpy as np
import pytest

from conftest import coord_service

from autodist_tpu.data import DataLoader, write_records

HAVE_GXX = shutil.which('g++') is not None
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')


def test_coord_kv_and_counters(coord):
    c = coord()
    c.set('k', 'v1')
    assert c.get('k') == 'v1'
    assert c.get('missing') is None
    assert c.incr('n', 3) == 3
    assert c.incr('n', 4) == 7
    c.delete('n')
    assert c.incr('n', 1) == 1


def test_coord_barrier_three_parties(coord):
    done = []

    def party(i):
        coord().barrier('b', 3, timeout_s=10.0)
        done.append(i)

    ts = [threading.Thread(target=party, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(15)
    assert sorted(done) == [0, 1, 2]


def test_coord_staleness_gate(coord):
    """c9 semantics (reference cases/c9.py:14-21): a worker may run at
    most ``staleness`` steps ahead of the slowest worker."""
    c = coord()
    c.publish_step('wa', 5)
    c.publish_step('wb', 3)
    c.staleness_gate(5, 2, num_workers=2, timeout_s=2.0)  # min 3 >= 3
    with pytest.raises(TimeoutError):
        c.staleness_gate(8, 2, num_workers=2, timeout_s=0.4)
    # both workers advance past step 6 -> the gate for step 8 opens
    def catch_up():
        cl = coord()
        cl.publish_step('wa', 7)
        cl.publish_step('wb', 6)
    t = threading.Timer(0.2, catch_up)
    t.start()
    c.staleness_gate(8, 2, num_workers=2, timeout_s=5.0)
    t.join()


def test_tensor_data_plane_binary_roundtrip(coord):
    """BSET/BGET/BADD binary frames: raw f32 bytes, no base64."""
    c = coord()
    rng = np.random.RandomState(1)
    t = rng.randn(1000).astype(np.float32)
    c.vset('t1', t)
    np.testing.assert_array_equal(c.vget('t1'), t)
    assert c.vadd('t1', t) == 1
    np.testing.assert_allclose(c.vget('t1'), 2 * t, rtol=1e-6)
    # BADD creates the tensor when absent (accumulator semantics)
    assert c.vadd('t_created', t) == 1
    np.testing.assert_array_equal(c.vget('t_created'), t)
    assert c.vget('absent') is None


def test_tensor_data_plane_large_tensor_streams(coord):
    """Multi-MB frames stream through the chunked recv path intact."""
    c = coord()
    rng = np.random.RandomState(2)
    t = rng.randn(2_000_000).astype(np.float32)   # 8 MB payload
    c.vset('big', t)
    np.testing.assert_array_equal(c.vget('big'), t)
    c.vadd('big', t)
    np.testing.assert_allclose(c.vget('big'), 2 * t, rtol=1e-6)


def test_tensor_data_plane_bf16_wire(coord):
    """bf16 wire: half the bytes; values rounded to bf16 on the wire,
    f32 at rest."""
    import ml_dtypes
    c = coord()
    t = np.linspace(-3.0, 3.0, 257).astype(np.float32)
    c.vset('tb', t, wire='bf16')
    want = t.astype(ml_dtypes.bfloat16).astype(np.float32)
    # stored values are exactly the bf16-rounded ones
    np.testing.assert_array_equal(c.vget('tb'), want)
    # a bf16 read of bf16-representable data is lossless
    np.testing.assert_array_equal(c.vget('tb', wire='bf16'), want)


def test_tensor_data_plane_shape_mismatch_rejected(coord):
    c = coord()
    c.vset('sm', np.zeros(8, np.float32))
    with pytest.raises(OSError, match='shape mismatch'):
        c.vadd('sm', np.zeros(4, np.float32))


def test_tensor_data_plane_server_side_optimizer(coord):
    """BSTEP: the optimizer step runs ON the PS with a service-resident
    velocity slot shared by every pusher (reference PS-resident
    optimizer, kernel/partitioner.py:570-573)."""
    c = coord()
    c.vset('w', np.ones(4, np.float32))
    g = np.full(4, 2.0, np.float32)
    assert c.vstep('w', g, 'sgd', [0.1, 0.9]) == 1
    # vel = 2.0; w = 1 - 0.1*2 = 0.8
    np.testing.assert_allclose(c.vget('w'), np.full(4, 0.8), rtol=1e-6)
    assert c.vstep('w', g, 'sgd', [0.1, 0.9]) == 2
    # vel = 0.9*2 + 2 = 3.8; w = 0.8 - 0.38 = 0.42
    np.testing.assert_allclose(c.vget('w'), np.full(4, 0.42), rtol=1e-6)
    # plain SGD path (momentum=0) never allocates a velocity slot
    c.vset('w2', np.zeros(2, np.float32))
    c.vstep('w2', np.ones(2, np.float32), 'sgd', [0.5])
    np.testing.assert_allclose(c.vget('w2'), np.full(2, -0.5), rtol=1e-6)
    with pytest.raises(OSError, match='no tensor'):
        c.vstep('w_absent', g, 'sgd', [0.1])
    with pytest.raises(OSError, match='unknown rule'):
        c.vset('w3', np.zeros(2, np.float32))
        c.vstep('w3', np.ones(2, np.float32), 'rprop', [0.1])


def test_tensor_data_plane_adam_matches_optax(coord):
    """BSTEP rule=adam: PS-resident (m, v, t) slots; the trajectory
    matches optax.adam exactly (same bias correction, eps outside the
    sqrt) — the reference's PS-resident-optimizer semantics for the
    user's ACTUAL optimizer, kernel/partitioner.py:570-573."""
    import jax.numpy as jnp
    import optax
    c = coord()
    w0 = np.array([1.0, -2.0, 0.5, 3.0], np.float32)
    grads = [np.array([0.3, -1.2, 2.0, 0.05], np.float32),
             np.array([-0.5, 0.7, 0.1, 1.0], np.float32),
             np.array([0.2, 0.2, -0.4, 0.9], np.float32)]
    lr, b1, b2, eps = 0.1, 0.9, 0.999, 1e-7
    tx = optax.adam(lr, b1=b1, b2=b2, eps=eps)
    state = tx.init(jnp.asarray(w0))
    w = jnp.asarray(w0)
    c.vset('adam_w', w0)
    for t, g in enumerate(grads, 1):
        u, state = tx.update(jnp.asarray(g), state, w)
        w = w + u
        assert c.vstep('adam_w', g, 'adam', [lr, b1, b2, eps]) == t
        np.testing.assert_allclose(c.vget('adam_w'), np.asarray(w),
                                   rtol=2e-4, atol=2e-6)


def test_tensor_data_plane_adagrad_matches_optax(coord):
    """BSTEP rule=adagrad: PS-resident accumulator (with the TF-style
    initial value); trajectory matches optax.adagrad."""
    import jax.numpy as jnp
    import optax
    c = coord()
    w0 = np.array([1.0, 2.0, 3.0], np.float32)
    grads = [np.array([0.3, -1.2, 2.0], np.float32),
             np.array([-0.5, 0.7, 0.1], np.float32)]
    lr, eps, init_acc = 0.1, 1e-7, 0.1
    tx = optax.adagrad(lr, initial_accumulator_value=init_acc, eps=eps)
    state = tx.init(jnp.asarray(w0))
    w = jnp.asarray(w0)
    c.vset('ada_w', w0)
    for g in grads:
        u, state = tx.update(jnp.asarray(g), state, w)
        w = w + u
        c.vstep('ada_w', g, 'adagrad', [lr, eps, init_acc])
        np.testing.assert_allclose(c.vget('ada_w'), np.asarray(w),
                                   rtol=1e-5, atol=1e-7)


def test_tensor_data_plane_chunked_frames(coord, monkeypatch):
    """Frames above AUTODIST_PS_CHUNK_BYTES move as ranged chunks;
    set/get/add/step all reassemble exactly (every rule is elementwise,
    so ranged application is exact — including adam's shared t)."""
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', str(4096))
    c = coord()
    rng = np.random.RandomState(7)
    t = rng.randn(5000).astype(np.float32)       # 20 KB -> 5 chunks
    c.vset('chunked', t)
    np.testing.assert_array_equal(c.vget('chunked', shape=(5000,)), t)
    assert c.vadd('chunked', t) == 1             # ONE logical push
    np.testing.assert_allclose(c.vget('chunked', shape=(5000,)), 2 * t,
                               rtol=1e-6)
    # chunked BSTEP shares one t across chunks (adam bias correction)
    g = rng.randn(5000).astype(np.float32)
    assert c.vstep('chunked', g, 'adam', [0.1, 0.9, 0.999, 1e-7]) == 1
    assert c.vstep('chunked', g, 'adam', [0.1, 0.9, 0.999, 1e-7]) == 2
    # uneven tail chunk (5000 elems % 1024-elem chunks != 0) landed too
    single = coord()
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', str(1 << 30))
    np.testing.assert_array_equal(
        single.vget('chunked', shape=(5000,)),
        c.vget('chunked', shape=(5000,)))


def test_tensor_data_plane_ranged_get(coord):
    """BGET with an explicit (offset, count) range returns that slice —
    the shard-ranged read primitive."""
    c = coord()
    t = np.arange(100, dtype=np.float32)
    c.vset('ranged', t)
    resp = c._rpc('BGET ranged f32 10 5')
    assert resp.startswith('VAL')
    got = np.frombuffer(c._read_exact(int(resp.split()[1])), np.float32)
    np.testing.assert_array_equal(got, t[10:15])
    assert c._rpc('BGET ranged f32 96 10').startswith('ERR bad range')


def test_torn_read_detection(coord, monkeypatch):
    """A chunked write in flight is visible to readers (ADVICE r4):
    BGET's opt-in version field is odd while any chunked BSET/BADD
    sequence is between its first and final chunk, and vget refuses to
    return the half-written tensor."""
    from autodist_tpu.runtime.coord_client import CoordClient
    monkeypatch.setattr(CoordClient, 'STALL_TIMEOUT_S', 0.3)
    c = coord()
    w = coord()
    t = np.arange(10, dtype=np.float32)
    c.vset('seq', t)
    resp = c._rpc('BGET seq f32 v')
    fields = resp.split()
    c._read_exact(int(fields[1]))
    assert len(fields) == 3 and int(fields[2]) % 2 == 0
    # writer sends only the FIRST chunk of a 2-chunk reset
    half = t[:5].tobytes()
    assert w._rpc('BSET seq %d f32 0 10' % len(half), half) == 'OK'
    resp = c._rpc('BGET seq f32 v')
    fields = resp.split()
    c._read_exact(int(fields[1]))
    assert int(fields[2]) % 2 == 1  # write in flight
    with pytest.raises(OSError, match='stuck mid-flight'):
        c.vget('seq', shape=(10,))
    # final chunk lands -> even version, reads succeed again
    assert w._rpc('BSET seq %d f32 5 10' % len(half),
                  t[5:].tobytes()) == 'OK'
    np.testing.assert_array_equal(c.vget('seq', shape=(10,)), t)
    # ranged reads carry the version too (chunk-mismatch detection)
    resp = c._rpc('BGET seq f32 0 5 v')
    fields = resp.split()
    c._read_exact(int(fields[1]))
    assert len(fields) == 3 and int(fields[2]) % 2 == 0
    # a REJECTED frame aborts the sequence it opened instead of wedging
    # readers on a permanently-odd version: open a sequence, then send
    # a chunk with a bad range
    assert w._rpc('BSET seq %d f32 0 10' % len(half), half) == 'OK'
    assert w._rpc('BSET seq %d f32 9 10' % len(half),
                  half).startswith('ERR bad range')
    resp = c._rpc('BGET seq f32 v')
    fields = resp.split()
    c._read_exact(int(fields[1]))
    assert int(fields[2]) % 2 == 0  # sequence aborted, reads flow


def test_malformed_offset0_frame_does_not_close_others_sequence(coord):
    """ISSUE 1 satellite: a REJECTED offset-0 frame never opened a
    sequence (SeqFrame is constructed after the payload/range checks),
    so it must NOT decrement open_writes — that would close another
    writer's in-flight chunked sequence and clear the torn-read parity
    bit under its feet."""
    c = coord()
    w = coord()
    evil = coord()
    t = np.arange(10, dtype=np.float32)
    c.vset('own', t)
    half = t[:5].tobytes()
    # w opens a 2-chunk sequence and stalls mid-flight
    assert w._rpc('BSET own %d f32 0 10' % len(half), half) == 'OK'

    def parity():
        resp = c._rpc('BGET own f32 v')
        fields = resp.split()
        c._read_exact(int(fields[1]))
        return int(fields[2]) % 2

    assert parity() == 1
    # another writer's malformed OFFSET-0 frames must not close it:
    # bad payload (3 bytes is not a whole f32)...
    assert evil._rpc('BADD own 3 f32', b'abc').startswith(
        'ERR bad payload')
    assert parity() == 1
    # ...and a bad range (negative offset)
    assert evil._rpc('BSET own %d f32 -1 10' % len(half), half) \
        .startswith('ERR bad range')
    assert parity() == 1
    # w completes; reads flow with the full value intact
    assert w._rpc('BSET own %d f32 5 10' % len(half),
                  t[5:].tobytes()) == 'OK'
    np.testing.assert_array_equal(c.vget('own', shape=(10,)), t)
    # a malformed CONTINUATION chunk (off>0) still aborts the open
    # sequence — that is the anti-wedge guard this satellite preserves
    assert w._rpc('BSET own %d f32 0 10' % len(half), half) == 'OK'
    assert parity() == 1
    assert evil._rpc('BADD own 3 f32 5 10', b'abc').startswith(
        'ERR bad payload')
    assert parity() == 0


def test_vget_even_parity_exhaustion_returns(coord, monkeypatch):
    """ISSUE 1 satellite: element-level staleness under frequent
    single-frame pushes is benign — when the version keeps ADVANCING
    with even parity past the (configurable) retry cap, vget returns
    the last assembly instead of killing a healthy worker; it raises
    only when parity is odd (genuinely mid-chunk)."""
    from autodist_tpu.runtime.coord_client import CoordClient
    monkeypatch.setenv('AUTODIST_PS_TORN_RETRIES', '3')
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '20')  # 5 f32/chunk
    c = coord()
    pusher = coord()
    t = np.arange(10, dtype=np.float32)
    c.vset('skew', t)
    real_send = CoordClient._send_frame

    def send_with_push(self, line, payload=None):
        # a whole single-frame push lands before every BGET request
        # frame goes out (vmget pipelines the frames, so this is the
        # pre-chunk hook), so the version advances (even parity)
        # between this pull's chunks on every attempt
        if self is c and line.startswith('BGET skew'):
            real_send(pusher, 'BADD skew 40 f32',
                      np.ones(10, np.float32).tobytes())
            assert pusher._read_reply_line().startswith('VAL')
        return real_send(self, line, payload)

    monkeypatch.setattr(CoordClient, '_send_frame', send_with_push)
    got = c.vget('skew', shape=(10,))   # must NOT raise
    assert got.shape == (10,)
    # rows are base + k pushes; chunks may straddle one push boundary
    base = np.arange(10, dtype=np.float32)
    k = got - base
    assert (k >= 1).all() and (k <= 16).all()
    assert np.ptp(k) <= 1   # at most one push of skew across chunks


def test_oversized_payload_declaration_refused(coord):
    """A header declaring an absurd payload size is refused immediately
    (ERR + close) instead of buffering toward it (ADVICE r3)."""
    import socket as _socket
    c = coord()
    addr = c.address
    for decl in (b'BADD k 99999999999999999999 f32\n',
                 b'BSET k 5000000000 f32\n'):
        s = _socket.create_connection(addr, timeout=5)
        s.recv(256)                    # greeting
        s.sendall(decl)
        s.settimeout(5)
        got = s.recv(256)
        assert b'ERR payload too large' in got or got == b''
        # connection is closed: further sends never get a reply
        s.close()
    c.ping()                           # service itself is healthy


def test_oversized_range_total_refused(coord):
    """A ranged B* command declaring an absurd <total> element count is
    refused (ERR bad range) instead of allocating toward it (review
    r4: unvalidated total would bad_alloc the whole service)."""
    c = coord()
    payload = np.zeros(1, np.float32).tobytes()
    resp = c._rpc('BSET big_total 4 f32 0 4000000000000000000', payload)
    assert resp.startswith('ERR bad range'), resp
    c.ping()


def test_auth_downgrade_refused(coord, monkeypatch):
    """A client configured with a token must refuse an OPEN service
    (stale/spoofed listener) instead of silently skipping auth."""
    from autodist_tpu.runtime.coord_client import CoordClient
    c0 = coord()   # fixture service runs open; this client pre-token
    monkeypatch.setenv('AUTODIST_COORD_TOKEN', 'configured-secret')
    with pytest.raises(OSError, match='downgrade'):
        CoordClient(c0.address, timeout=5)


def test_delete_namespace_purges_tensors_and_keys(coord):
    """DELNS: run-end cleanup for long-lived endpoint daemons — a dead
    run's tensors/counters/keys vanish; other namespaces survive."""
    c = coord()
    c.set('runA/k', 'v')
    c.incr('runA/step/p0', 3)
    c.vset('runA/var/w', np.ones(4, np.float32))
    c.set('runB/k', 'keep')
    c.vset('runB/var/w', np.ones(2, np.float32))
    assert c.delete_namespace('runA/') >= 3
    assert c.get('runA/k') is None
    assert c.vget('runA/var/w') is None
    assert c.incr('runA/step/p0', 0) == 0
    assert c.get('runB/k') == 'keep'
    np.testing.assert_array_equal(c.vget('runB/var/w'),
                                  np.ones(2, np.float32))


def test_tensor_data_plane_concurrent_pushes(coord):
    """Per-key tensor locks: concurrent pushes from many connections all
    land, and pushes to distinct keys do not serialize on one global
    lock (correctness side; scalability is the design point)."""
    c0 = coord()
    c0.vset('acc', np.zeros(10000, np.float32))
    c0.vset('acc2', np.zeros(10000, np.float32))

    def pusher(key):
        cl = coord()
        one = np.full(10000, 1.0, np.float32)
        for _ in range(5):
            cl.vadd(key, one)

    ts = [threading.Thread(target=pusher,
                           args=('acc' if i % 2 == 0 else 'acc2',))
          for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    np.testing.assert_allclose(c0.vget('acc'), 10.0)
    np.testing.assert_allclose(c0.vget('acc2'), 10.0)


def test_coord_service_survives_malformed_input(coord):
    """Garbage lines, unknown commands, and bogus binary headers get an
    ERR reply (or a clean disconnect) without taking the service down
    for other connections."""
    import socket as _socket
    c = coord()
    c.set('canary', 'alive')
    addr = c.address
    for payload in (b'\n', b'NOTACMD x y\n', b'BADD k notanum f32\n',
                    b'BGET\n', b'BSET k 12 q99\nxxxxxxxxxxxx'):
        s = _socket.create_connection(addr, timeout=5)
        s.sendall(payload)
        try:
            s.settimeout(5)
            s.recv(256)   # reply or clean close — either is fine
        except OSError:
            pass
        s.close()
    # the service is still healthy for existing and new connections
    assert c.get('canary') == 'alive'
    c2 = coord()
    c2.ping()


def test_coord_service_auth_handshake(monkeypatch, tmp_path):
    """AUTODIST_COORD_TOKEN: the service challenges every connection
    with a nonce; only HMAC-SHA256(token, nonce) gets in. Wrong token,
    missing token, and raw no-AUTH connections are all refused; the
    token-file transport (how the ssh coordinator ships the secret)
    resolves too."""
    import socket as _socket
    from autodist_tpu.runtime.coord_client import CoordClient
    monkeypatch.setenv('AUTODIST_COORD_TOKEN', 'sekrit-token')
    with coord_service() as port:
        c = CoordClient(('127.0.0.1', port), timeout=5)
        c.set('authed', 'yes')
        assert c.get('authed') == 'yes'
        # token-file transport (mode-0600 file, no env secret)
        monkeypatch.delenv('AUTODIST_COORD_TOKEN')
        tok_file = tmp_path / 'coord_token'
        tok_file.write_text('sekrit-token\n')
        monkeypatch.setenv('AUTODIST_COORD_TOKEN_FILE', str(tok_file))
        c2 = CoordClient(('127.0.0.1', port), timeout=5)
        assert c2.get('authed') == 'yes'
        monkeypatch.delenv('AUTODIST_COORD_TOKEN_FILE')
        # wrong token -> server refuses
        monkeypatch.setenv('AUTODIST_COORD_TOKEN', 'wrong')
        with pytest.raises(OSError, match='auth'):
            CoordClient(('127.0.0.1', port), timeout=5)
        # no token -> client refuses to even try
        monkeypatch.delenv('AUTODIST_COORD_TOKEN')
        with pytest.raises(OSError, match='auth'):
            CoordClient(('127.0.0.1', port), timeout=5)
        # raw connection skipping AUTH gets nothing but a refusal
        s = _socket.create_connection(('127.0.0.1', port), timeout=5)
        assert s.recv(256).startswith(b'HELLO ')
        s.sendall(b'GET authed\n')
        s.settimeout(5)
        got = s.recv(256)
        assert b'ERR auth' in got or got == b''
        s.close()
        # the authed connection still works
        assert c.get('authed') == 'yes'
        # the helper's shutdown needs the secret too
        monkeypatch.setenv('AUTODIST_COORD_TOKEN', 'sekrit-token')


@pytest.mark.parametrize('builder_name,rows,shard_sizes', [
    ('PartitionedPS', 6, [3, 3]),          # even split
    ('UnevenPartitionedPS', 7, [4, 3]),    # np.array_split semantics
])
def test_loose_partitioned_get_load_roundtrip(coord, monkeypatch,
                                              builder_name, rows,
                                              shard_sizes):
    """Single-process loose session over a PARTITIONED variable: the
    shard-keyed data plane serves get_variable_value (merge) and
    load_variable_value (split) exactly — the save/restore path of the
    per-shard placement (reference rebuilds savers over
    PartitionedVariables, kernel/partitioner.py:251-347), including
    UNEVEN shard sizes (uneven_partition_ps_strategy.py:125-133)."""
    import autodist_tpu as ad
    from autodist_tpu import autodist as ad_mod
    ad_mod._DEFAULT_AUTODIST.clear()
    host, port = coord().address
    monkeypatch.setenv('AUTODIST_COORD_SERVICE_ADDR',
                       '%s:%d' % (host, port))
    monkeypatch.setenv('AUTODIST_NUM_PROCESSES', '1')
    builder = getattr(ad.strategy, builder_name)(staleness=1)
    autodist = ad.AutoDist(
        resource_info={'nodes': [
            {'address': 'localhost', 'gpus': [0], 'chief': True,
             'network_bandwidth': 100}]},
        strategy_builder=builder)
    rng = np.random.RandomState(0)
    W0 = rng.randn(rows, 3).astype(np.float32)
    with autodist.scope():
        x = ad.placeholder(shape=[None, rows], dtype=np.float32,
                           name='x')
        W = ad.Variable(W0, name='W')
        loss = ad.ops.reduce_mean(ad.ops.square(ad.ops.matmul(x, W)))
        train_op = ad.optimizers.SGD(0.1).minimize(loss, [W])
        sess = autodist.create_distributed_session()
        plan = sess._plan.var_plans['W']
        assert plan.num_shards == len(shard_sizes)
        assert plan.part_config.shard_sizes(rows) == shard_sizes
        np.testing.assert_allclose(sess.get_variable_value('W'), W0,
                                   atol=1e-6)
        sess.run(train_op, {x: rng.randn(4, rows).astype(np.float32)})
        assert np.abs(sess.get_variable_value('W') - W0).max() > 1e-6
        # checkpoint-restore path: load splits across the shards
        sess.load_variable_value('W', W0)
        np.testing.assert_allclose(sess.get_variable_value('W'), W0,
                                   atol=1e-6)
        sess.close()


def test_dataloader_native_matches_python(tmp_path):
    rng = np.random.RandomState(0)
    data = rng.randint(0, 1000, (32, 16)).astype(np.int32)
    f = write_records(str(tmp_path / 'd.rec'), data)
    batches = {}
    for native in (True, False):
        dl = DataLoader([f], 8, (16,), np.int32, shuffle=False,
                        native=native)
        batches[native] = [dl.next_batch() for _ in range(4)]
        dl.close()
    for a, b in zip(batches[True], batches[False]):
        assert np.array_equal(a, b)
    assert np.array_equal(np.concatenate(batches[True]), data)


def test_dataloader_sharding_partitions_records(tmp_path):
    data = np.arange(64, dtype=np.int32).reshape(16, 4)
    f = write_records(str(tmp_path / 'd.rec'), data)
    seen = set()
    for shard in range(4):
        dl = DataLoader([f], 4, (4,), np.int32, shuffle=False,
                        shard_id=shard, num_shards=4, native=True)
        for row in dl.next_batch():
            seen.add(int(row[0]))
        dl.close()
    assert seen == {int(r[0]) for r in data}


def test_dataloader_shuffle_is_seeded(tmp_path):
    data = np.arange(160, dtype=np.int32).reshape(16, 10)
    f = write_records(str(tmp_path / 'd.rec'), data)

    def first_batch(seed):
        dl = DataLoader([f], 16, (10,), np.int32, shuffle=True,
                        seed=seed, native=True)
        out = dl.next_batch()
        dl.close()
        return out

    assert np.array_equal(first_batch(3), first_batch(3))
    assert not np.array_equal(first_batch(3), first_batch(4))


def test_coordinator_debug_remote(monkeypatch):
    """Coordinator emits the right ssh/scp commands (debug mode)."""
    monkeypatch.setenv('AUTODIST_DEBUG_REMOTE', 'True')
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.runtime.coordinator import Coordinator
    from autodist_tpu.strategy.base import Strategy
    spec = ResourceSpec(resource_info={'nodes': [
        {'address': '10.0.0.1', 'chief': True, 'gpus': [0], 'cpus': [0],
         'network_bandwidth': 10},
        {'address': '10.0.0.2', 'gpus': [0], 'cpus': [0],
         'network_bandwidth': 10}]})
    s = Strategy()
    s.serialize()
    c = Coordinator(s, spec)
    c.launch_clients()
    assert c.procs == []  # debug mode launches nothing
    env = c._worker_env('10.0.0.2', 1)
    assert env['AUTODIST_WORKER'] == '10.0.0.2'
    assert env['AUTODIST_STRATEGY_ID'] == s.id
    assert env['AUTODIST_NUM_PROCESSES'] == '2'


def test_prefetch_to_device_preserves_order_and_values(tmp_path):
    """Device prefetch keeps batch order/values and composes with the
    record loader + Trainer.fit (host IO || transfer || compute)."""
    import jax
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.data import DataLoader, prefetch_to_device, \
        write_records
    from autodist_tpu.models.core import Dense, Module
    from autodist_tpu.parallel.axes import ParallelSpec

    rng = np.random.RandomState(0)
    records = rng.rand(64, 4).astype('f4')
    f = write_records(str(tmp_path / 'r.adtr'), records)
    dl = DataLoader([f], 8, (4,), np.float32, shuffle=False, native=False)

    # raw order/value equivalence against a second, unprefetched pass
    # (the loader iterates forever across epochs — bound both sides)
    import itertools
    got = list(prefetch_to_device(itertools.islice(iter(dl), 8),
                                  lambda b: b, size=3))
    dl2 = DataLoader([f], 8, (4,), np.float32, shuffle=False,
                     native=False)
    want = list(itertools.islice(iter(dl2), 8))
    assert len(got) == len(want) == 8
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)

    class Reg(Module):
        def __init__(self):
            self.lin = Dense(3, 1, 'in', 'out')

        def param_defs(self):
            return {'lin': self.lin}

        def loss(self, params, batch):
            pred = self.lin.apply(params['lin'], batch['x'])[:, 0]
            return ((pred - batch['y']) ** 2).mean()

    def batches(n):
        for i in range(n):
            yield {'x': records[(8 * i) % 56:(8 * i) % 56 + 8, :3],
                   'y': records[(8 * i) % 56:(8 * i) % 56 + 8, 3]}

    tr = Trainer(Reg(), optax.sgd(0.1), spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    _, hist_plain = tr.fit(state, batches(6))
    state2 = tr.init(jax.random.PRNGKey(0))
    _, hist_pref = tr.fit(state2, batches(6), prefetch=2)
    np.testing.assert_allclose(hist_plain['loss'], hist_pref['loss'],
                               rtol=1e-6)


def test_prefetch_size_validation():
    from autodist_tpu.data import prefetch_to_device
    import pytest as _pytest
    with _pytest.raises(ValueError, match='>= 1'):
        list(prefetch_to_device([1, 2], lambda x: x, size=0))


def test_prefetch_defers_source_error_until_drained():
    """Batches already placed must be yielded before a source error
    surfaces — no silent loss of completed transfers."""
    from autodist_tpu.data import prefetch_to_device

    def source():
        yield 1
        yield 2
        raise IOError('disk gone')

    got = []
    import pytest as _pytest
    with _pytest.raises(IOError, match='disk gone'):
        for b in prefetch_to_device(source(), lambda x: x * 10, size=3):
            got.append(b)
    assert got == [10, 20]


# -- the build and the service fixture themselves ---------------------------

_BUILD_AND_RUN = '''
import subprocess, sys
from autodist_tpu import native_build
native_build.NATIVE_CACHE_DIR = sys.argv[1]
print('ready', flush=True)
sys.stdin.read()                      # the starting gun
out = native_build.build('coord_service.cc')
# 192.0.2.1 (TEST-NET-1) is no address of this host: the service execs,
# cannot bind and exits 1
rc = subprocess.run([out, '0', '192.0.2.1'], stderr=subprocess.DEVNULL,
                    timeout=60).returncode
sys.exit(0 if rc == 1 else 10 + rc)
'''


def test_build_is_whole_under_concurrent_callers(tmp_path):
    """Several processes that reach ``build`` together on a cold cache
    each get an artifact they can execute at once: the file at the
    final path is never one a linker still holds open (ETXTBSY)."""
    import contextlib
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=REPO)
    with contextlib.ExitStack() as stack:
        procs = [stack.enter_context(subprocess.Popen(
            [sys.executable, '-c', _BUILD_AND_RUN, str(tmp_path)],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)) for _ in range(6)]
        stack.callback(lambda: [p.kill() for p in procs])   # before the waits
        for p in procs:     # all are past their imports before any builds
            assert p.stdout.readline() == 'ready\n', p.stderr.read()
        for p in procs:
            p.stdin.close()
        codes = [p.wait(timeout=300) for p in procs]
        assert codes == [0] * 6, [p.stderr.read() for p in procs]
    (digest,) = os.listdir(tmp_path)
    assert os.listdir(tmp_path / digest) == ['coord_service']


def test_failed_compile_leaves_no_artifact(tmp_path, monkeypatch):
    """A compile that fails leaves nothing at the artifact's path (an
    ``exists`` check would otherwise hand out the wreck for ever), and
    the next call builds."""
    import subprocess

    from autodist_tpu import native_build
    monkeypatch.setattr(native_build, 'NATIVE_CACHE_DIR', str(tmp_path))
    with pytest.raises(subprocess.CalledProcessError):
        native_build.build('dataloader.cc', shared=True,
                           extra_flags=('-l:no-such-library',))
    assert [os.listdir(tmp_path / d) for d in os.listdir(tmp_path)] == [[]]
    out = native_build.build('dataloader.cc', shared=True)
    assert os.path.getsize(out) > 0


def test_service_fixture_survives_a_taken_port():
    """Handed a port that something else holds, the shared helper ends
    with a live service on another one."""
    import socket

    from autodist_tpu.runtime.coord_client import CoordClient
    with socket.socket() as held:
        held.bind(('127.0.0.1', 0))
        held.listen(1)
        taken = held.getsockname()[1]
        with coord_service(port=taken) as port:
            assert port != taken
            c = CoordClient(('127.0.0.1', port))
            c.set('k', 'v')
            assert c.get('k') == 'v'
    with pytest.raises(OSError):        # and it is shut down on exit
        CoordClient(('127.0.0.1', port), timeout=0.5).ping()
