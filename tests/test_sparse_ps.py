"""Row-sparse PS data plane: BSADD/BGETROWS protocol, the session's
runtime sparsity detection + threshold, lazy optimizers, and the
protocol-doc drift check (tools/check_protocol.py).

Protocol tests talk to a real coord_service (built on demand, skipped
without g++); session tests ride the single-process loose harness the
async-PS suite uses.
"""
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HAVE_GXX = shutil.which('g++') is not None

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_gxx = pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')


# -- protocol: BSADD / BGETROWS ------------------------------------------

@needs_gxx
@pytest.mark.parametrize('wire', ['f32', 'bf16'])
@pytest.mark.parametrize('chunked', [False, True])
def test_bsadd_matches_dense_badd(coord, monkeypatch, wire, chunked):
    """A sparse push of the touched rows must land element-identically
    to a dense BADD of the equivalent delta (zero rows dropped), for
    whole-push frames and row-chunked sequences, on both wire dtypes.
    Indices are unique here: under bf16 a dense push rounds the
    PRE-accumulated sum while duplicate sparse rows round per row (see
    the duplicates test for the f32 accumulation contract)."""
    if chunked:
        monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '256')
    c = coord()
    rng = np.random.RandomState(3)
    table = rng.randn(64, 8).astype(np.float32)
    idx = rng.permutation(64)[:24].astype(np.int32)
    rows = rng.randn(24, 8).astype(np.float32)
    dense = np.zeros((64, 8), np.float32)
    dense[idx] = rows
    ks = 'sp/%s%d/s' % (wire, chunked)
    kd = 'sp/%s%d/d' % (wire, chunked)
    c.vset(ks, table)
    c.vset(kd, table)
    assert c.vsadd(ks, idx, rows, wire=wire) == 1
    c.vadd(kd, dense, wire=wire)
    np.testing.assert_array_equal(c.vget(ks, shape=(64, 8)),
                                  c.vget(kd, shape=(64, 8)))


@needs_gxx
def test_bsadd_duplicate_indices_accumulate(coord):
    """Scatter-add semantics: a row index listed k times accumulates
    all k rows (gradients of repeated batch ids sum, exactly like the
    dense delta they came from)."""
    c = coord()
    c.vset('dup/t', np.zeros((8, 4), np.float32))
    idx = np.array([3, 3, 3, 5], np.int32)
    rows = np.ones((4, 4), np.float32)
    c.vsadd('dup/t', idx, rows)
    got = c.vget('dup/t', shape=(8, 4))
    np.testing.assert_array_equal(got[3], np.full(4, 3.0, np.float32))
    np.testing.assert_array_equal(got[5], np.ones(4, np.float32))
    assert got[[0, 1, 2, 4, 6, 7]].sum() == 0.0


@needs_gxx
@pytest.mark.parametrize('wire', ['f32', 'bf16'])
def test_bgetrows_matches_full_bget(coord, wire):
    c = coord()
    rng = np.random.RandomState(4)
    table = rng.randn(32, 6).astype(np.float32)
    c.vset('gr/t', table)
    idx = np.array([0, 31, 7, 7, 13], np.int32)
    rows = c.vgetrows('gr/t', idx, 6, wire=wire)
    full = c.vget('gr/t', shape=(32, 6), wire=wire)
    np.testing.assert_array_equal(rows, full[idx])
    assert c.vgetrows('gr/absent', [0], 6) is None


@needs_gxx
def test_bsadd_requires_existing_tensor_and_valid_rows(coord):
    c = coord()
    with pytest.raises(OSError, match='no tensor'):
        c.vsadd('spnone/t', [0], np.ones((1, 4), np.float32))
    c.vset('spbad/t', np.zeros((4, 4), np.float32))
    with pytest.raises(OSError, match='bad row index'):
        c.vsadd('spbad/t', [4], np.ones((1, 4), np.float32))
    with pytest.raises(OSError, match='bad row index'):
        c.vgetrows('spbad/t', [99], 4)


@needs_gxx
def test_bgetrows_oversized_reply_refused(coord):
    """A huge declared reply (nrows x ncols) must be refused before
    any allocation — an unvalidated product could bad_alloc (or wrap
    size_t) and kill the whole control plane."""
    c = coord()
    c.vset('cap/t', np.zeros((8, 4), np.float32))
    idx = np.ascontiguousarray(np.zeros(1000, np.int32))
    resp = c._rpc('BGETROWS cap/t 1000 16000000 f32',
                  memoryview(idx).cast('B'))
    assert resp == 'ERR reply too large'
    c.ping()   # service healthy; the normal path still works
    assert c.vgetrows('cap/t', [1, 2], 4).shape == (2, 4)


@needs_gxx
def test_fence_rejects_zombie_bsadd(coord):
    """A sparse push is a mutation like any other: once the writer's
    fencing generation is superseded, BSADD returns ERR fenced and the
    client surfaces the typed error."""
    from autodist_tpu.runtime.coord_client import FencedWriteError
    c = coord()
    other = coord()
    c.vset('fz/t', np.zeros((8, 4), np.float32))
    gen = c.incr('fence/spz', 0)
    c.fence('fence/spz', gen)
    other.incr('fence/spz', 1)   # supersede the writer
    with pytest.raises(FencedWriteError):
        c.vsadd('fz/t', [1], np.ones((1, 4), np.float32))
    # the tensor is untouched
    np.testing.assert_array_equal(other.vget('fz/t', shape=(8, 4)),
                                  np.zeros((8, 4), np.float32))


@needs_gxx
def test_disconnect_aborts_bsadd_chunk_sequence(coord, monkeypatch):
    """A writer that dies between BSADD row chunks must not wedge
    readers on odd parity: the service aborts the connection's open
    sequences at disconnect (the same SeqAborter path as BADD)."""
    from autodist_tpu.runtime.coord_client import CoordClient
    monkeypatch.setattr(CoordClient, 'STALL_TIMEOUT_S', 0.3)
    w = coord()
    reader = coord()
    w.vset('dcs/t', np.zeros((16, 4), np.float32))
    # hand-send ONLY the opening chunk of a declared 2-row sequence
    idx = np.ascontiguousarray(np.array([2], np.int32))
    row = np.ones((1, 4), np.float32)
    resp = w._rpc('BSADD dcs/t 1 16 f32 0 2',
                  [memoryview(idx).cast('B'),
                   memoryview(row.reshape(-1)).cast('B')])
    assert resp.startswith('VAL')
    w.close()                    # writer dies mid-sequence
    deadline = time.time() + 5.0
    while True:                  # service thread observes the EOF
        try:
            got = reader.vget('dcs/t', shape=(16, 4))
            break
        except OSError:
            if time.time() > deadline:
                raise
            time.sleep(0.05)
    np.testing.assert_array_equal(got[2], np.ones(4, np.float32))


@needs_gxx
def test_torn_frame_over_sparse_write(coord, monkeypatch):
    """faultline's torn_frame rewrites a whole-push BSADD into the
    opening chunk of a 2x-row sequence whose continuation never comes:
    readers — dense BGET and row-read BGETROWS alike — must surface
    the mid-flight error instead of torn data."""
    from autodist_tpu.runtime.coord_client import CoordClient
    from autodist_tpu.utils.faultline import FaultLine, FaultPlan
    monkeypatch.setattr(CoordClient, 'STALL_TIMEOUT_S', 0.3)
    monkeypatch.setenv('AUTODIST_PS_TORN_RETRIES', '5')
    w = coord()
    reader = coord()
    w.vset('tfs/t', np.zeros((8, 4), np.float32))
    plan = FaultPlan([{'kind': 'torn_frame', 'match': 'BSADD tfs/t'}])
    with FaultLine(plan) as fl:
        w.vsadd('tfs/t', [2], np.ones((1, 4), np.float32))
        with pytest.raises(OSError, match='dead'):
            w.vsadd('tfs/t', [3], np.ones((1, 4), np.float32))
    with pytest.raises(OSError, match='mid-flight'):
        reader.vget('tfs/t', shape=(8, 4))
    with pytest.raises(OSError, match='mid-flight'):
        reader.vgetrows('tfs/t', [2], 4)
    assert fl.events[0]['kind'] == 'torn_frame'


# -- session: runtime sparsity detection ---------------------------------

def _classify(sparse_vars, deltas):
    from autodist_tpu.runtime.session import Session
    return Session._classify_push(
        SimpleNamespace(_sparse_vars=set(sparse_vars)), deltas)


def test_classify_push_threshold_crossover(monkeypatch):
    """At the default 0.5 threshold: few touched rows go sparse, many
    go dense, all-zero deltas are skipped outright, and the env knob
    moves the crossover (0 disables the sparse plane)."""
    few = np.zeros((10, 4), np.float32)
    few[[1, 5, 7]] = 1.0
    many = np.zeros((10, 4), np.float32)
    many[:6] = 1.0

    zero_skip, sparse = _classify({'E'}, {'E': few})
    assert not zero_skip and list(sparse['E']) == [1, 5, 7]

    zero_skip, sparse = _classify({'E'}, {'E': many})
    assert not zero_skip and not sparse       # 0.6 > 0.5 -> dense

    zero_skip, sparse = _classify({'E'},
                                  {'E': np.zeros((10, 4), np.float32)})
    assert zero_skip == {'E'} and not sparse  # frozen var: no push

    # a dense-flagged var never goes sparse, however sparse its delta
    zero_skip, sparse = _classify(set(), {'W': few})
    assert not zero_skip and not sparse

    monkeypatch.setenv('AUTODIST_SPARSE_PUSH_MAX_FRAC', '0.7')
    _, sparse = _classify({'E'}, {'E': many})
    assert list(sparse['E']) == [0, 1, 2, 3, 4, 5]

    monkeypatch.setenv('AUTODIST_SPARSE_PUSH_MAX_FRAC', '0')
    _, sparse = _classify({'E'}, {'E': few})
    assert not sparse                          # sparse plane disabled


def test_sparse_push_frac_env_validated(monkeypatch):
    from autodist_tpu.const import ENV
    monkeypatch.setenv('AUTODIST_SPARSE_PUSH_MAX_FRAC', '1.5')
    with pytest.raises(ValueError, match='AUTODIST_SPARSE_PUSH_MAX_FRAC'):
        ENV.AUTODIST_SPARSE_PUSH_MAX_FRAC.val


# -- lazy optimizers ------------------------------------------------------

def test_lazy_adam_keeps_untouched_rows_bit_stable():
    """LazyAdam: rows with zero gradient keep weights AND moments
    bit-identical across steps — including rows touched earlier, whose
    plain-Adam moments would otherwise keep moving them."""
    import jax.numpy as jnp

    import autodist_tpu as ad

    rng = np.random.RandomState(0)
    w0 = rng.randn(6, 3).astype(np.float32)
    lazy = ad.optimizers.LazyAdam(0.1)
    w = jnp.asarray(w0)
    state = lazy.tx.init(w)

    g1 = np.zeros((6, 3), np.float32)
    g1[2] = 1.0
    w1, state = lazy._lazy_row_update(jnp.asarray(g1), state, w)
    w1 = np.asarray(w1)
    untouched = [0, 1, 3, 4, 5]
    assert np.array_equal(w1[untouched], w0[untouched])
    assert not np.array_equal(w1[2], w0[2])

    # step 2 touches a DIFFERENT row: row 2 (touched at step 1, moments
    # now nonzero) must stay bit-stable under the lazy rule
    g2 = np.zeros((6, 3), np.float32)
    g2[4] = -0.5
    w2, state = lazy._lazy_row_update(jnp.asarray(g2), state,
                                      jnp.asarray(w1))
    w2 = np.asarray(w2)
    assert np.array_equal(w2[2], w1[2])
    assert not np.array_equal(w2[4], w1[4])

    # contrast: plain Adam's decayed moments move row 2 on step 2 —
    # the densifying behavior LazyAdam exists to prevent
    plain = ad.optimizers.Adam(0.1)
    ps = plain.tx.init(jnp.asarray(w0))
    u1, ps = plain.tx.update(jnp.asarray(g1), ps, jnp.asarray(w0))
    pw1 = np.asarray(jnp.asarray(w0) + u1)
    u2, ps = plain.tx.update(jnp.asarray(g2), ps, jnp.asarray(pw1))
    pw2 = np.asarray(jnp.asarray(pw1) + u2)
    assert not np.array_equal(pw2[2], pw1[2])


def test_lazy_momentum_row_stability():
    import jax.numpy as jnp

    import autodist_tpu as ad

    w0 = np.ones((4, 2), np.float32)
    opt = ad.optimizers.LazyMomentum(0.1, momentum=0.9)
    state = opt.tx.init(jnp.asarray(w0))
    g = np.zeros((4, 2), np.float32)
    g[1] = 2.0
    w1, state = opt._lazy_row_update(jnp.asarray(g), state,
                                     jnp.asarray(w0))
    w1 = np.asarray(w1)
    assert np.array_equal(w1[[0, 2, 3]], w0[[0, 2, 3]])
    # zero-grad step: velocity decay must NOT leak into row 1
    z = np.zeros((4, 2), np.float32)
    w2, state = opt._lazy_row_update(jnp.asarray(z), state,
                                     jnp.asarray(w1))
    assert np.array_equal(np.asarray(w2), w1)


# -- end-to-end: loose-mode sparse plane ---------------------------------

def _loose_embedding_run(port, max_frac, steps=3, vocab=96, dim=8):
    import autodist_tpu as ad
    from autodist_tpu.utils.loose_harness import single_process_loose_env

    saved = os.environ.get('AUTODIST_SPARSE_PUSH_MAX_FRAC')
    os.environ['AUTODIST_SPARSE_PUSH_MAX_FRAC'] = str(max_frac)
    try:
        with single_process_loose_env(port, depth=1) as sees_one:
            autodist = ad.AutoDist(
                resource_info={'nodes': [
                    {'address': 'localhost', 'gpus': [0], 'chief': True,
                     'network_bandwidth': 100}]},
                strategy_builder=ad.strategy.PS(
                    staleness=2, local_proxy_variable=True))
            rng = np.random.RandomState(0)
            E0 = rng.randn(vocab, dim).astype(np.float32)
            U0 = rng.randn(4, 2).astype(np.float32)
            ids = np.array([5, 5, 11, 60], np.int32)
            with autodist.scope():
                x = ad.placeholder(shape=[None], dtype=np.int32,
                                   name='ids')
                E = ad.Variable(E0, name='E')
                U = ad.Variable(U0, name='U')   # unused: zero grads
                emb = ad.ops.embedding_lookup(E, x)
                loss = ad.ops.reduce_mean(ad.ops.square(emb))
                train_op = ad.optimizers.LazyAdam(0.05).minimize(
                    loss, [E, U])
                autodist._build()
                ns = autodist._transformed[0].id
                sees_one()
                sess = autodist.create_distributed_session()
                for _ in range(steps):
                    sess.run(train_op, {x: ids})
                stats = sess.ps_stats
                final = sess.get_variable_value('E')
                from autodist_tpu.runtime.coord_client import CoordClient
                pushes = CoordClient(
                    ('127.0.0.1', port)).vstat('%s/var/U' % ns)
                sess.close()
            return final, stats, E0, pushes
    finally:
        if saved is None:
            os.environ.pop('AUTODIST_SPARSE_PUSH_MAX_FRAC', None)
        else:
            os.environ['AUTODIST_SPARSE_PUSH_MAX_FRAC'] = saved


@needs_gxx
def test_session_sparse_plane_matches_dense_and_skips_zero(coord_port):
    """The whole vertical slice: a loose-mode embedding run on the
    sparse plane lands bit-identically to the dense plane, moves fewer
    bytes, keeps untouched rows at their initial values (LazyAdam), and
    never pushes the frozen variable's all-zero delta (BSTAT push
    count stays at the chief's seed)."""
    dense_final, dense_stats, E0, dense_upushes = \
        _loose_embedding_run(coord_port, 0.0)
    sparse_final, sparse_stats, _, sparse_upushes = \
        _loose_embedding_run(coord_port, 0.5)

    assert np.array_equal(dense_final, sparse_final)
    ss = sparse_stats['sparse']
    assert ss['sparse_pushes'] == 3
    assert ss['rows_pushed'] == 9          # 3 distinct ids x 3 steps
    assert ss['zero_push_skips'] == 3      # U every step
    assert ss['dense_bytes_avoided'] > 0
    assert sparse_stats['bytes'] < dense_stats['bytes']
    assert dense_stats['sparse']['sparse_pushes'] == 0
    # the frozen var's tensor saw ONLY the chief's seeding BSET
    assert sparse_upushes is not None and sparse_upushes['pushes'] == 0
    # untouched embedding rows never left their init values
    untouched = np.setdiff1d(np.arange(96), [5, 11, 60])
    np.testing.assert_array_equal(sparse_final[untouched], E0[untouched])


def test_ps_sparse_report_ratios():
    from autodist_tpu.utils.profiling import (format_ps_sparse,
                                              ps_sparse_report)
    stats = {'bytes': 1000,
             'sparse': {'sparse_pushes': 3, 'rows_pushed': 9,
                        'dense_bytes_avoided': 9000,
                        'zero_push_skips': 1, 'row_refreshes': 2,
                        'rows_refreshed': 6, 'full_refreshes': 1}}
    rep = ps_sparse_report(stats)
    assert abs(rep['avoided_frac'] - 0.9) < 1e-9
    assert 'sparse pushes 3' in format_ps_sparse(rep)
    assert ps_sparse_report({}) == {}
    assert ps_sparse_report({'bytes': 5}) == {}
    assert format_ps_sparse({}) == '(no sparse-plane counters)'


# -- protocol-doc drift check (analysis/fence_lint, shim:
# tools/check_protocol.py) ------------------------------------------------

def test_protocol_header_matches_dispatch():
    """The coord_service header comment's command table must list
    exactly the dispatcher's commands (plus handshake-only AUTH) —
    the two drifted once (BSTAT) before this check existed. Runs
    through the analyzer now; the tools/check_protocol.py shim must
    keep the documented CLI invocation alive."""
    from autodist_tpu.analysis import fence_lint
    assert fence_lint.find_drift() == []
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools',
                                      'check_protocol.py')],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_protocol_checker_catches_drift():
    from autodist_tpu.analysis import fence_lint as cp
    text = open(cp.SRC).read()
    assert not cp.find_drift(text)
    # an undocumented dispatched command must be flagged
    broken = text.replace('if (cmd == "PING")',
                          'if (cmd == "BOGUS") return "OK";\n'
                          '  if (cmd == "PING")')
    assert any('BOGUS' in p for p in cp.find_drift(broken))
    # a documented-but-undispatched command must be flagged
    broken2 = text.replace('//   PING ',
                           '//   GHOSTCMD <x> -> OK\n//   PING ')
    assert any('GHOSTCMD' in p for p in cp.find_drift(broken2))
