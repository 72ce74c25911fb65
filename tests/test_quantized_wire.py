"""Block-quantized comms: the i8 PS wire + the bucketed int8 sync path.

Covers ISSUE 8's test satellite: golden-frame round-trips for
``encode_wire``/``decode_wire`` across all three wire dtypes (bf16
NaN/round-to-nearest-even edges, i8 blocks that do not divide the
tensor length), the end-to-end loose-mode run on the i8 wire (bounded
divergence vs f32, exact error-feedback residual carry, 2-worker
accumulation), the bucket-level Int8RingCompressor path, and the
wire-pricing drift check (tools/check_wire_pricing.py).
"""
import os
import shutil
import struct
import threading

import numpy as np
import pytest

from autodist_tpu.runtime import coord_client as cc

HAVE_GXX = shutil.which('g++') is not None


# -- wire-pricing drift check (analysis/schedule_lint, shim:
# tools/check_wire_pricing.py) -------------------------------------------

def test_wire_itemsize_matches_compressor_registry():
    """A compressor missing from cost_model._WIRE_ITEMSIZE silently
    prices as f32 — the simulator could then never rank the tier the
    compressor exists for. Runs through the analyzer now; the
    tools/check_wire_pricing.py shim must keep the documented CLI
    entry alive."""
    import importlib.util
    from autodist_tpu.analysis.schedule_lint import check_wire_pricing
    assert check_wire_pricing() == []
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools', 'check_wire_pricing.py')
    spec = importlib.util.spec_from_file_location('check_wire_pricing',
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.find_drift() == []


# -- golden frames: the Python encoder/decoder ---------------------------

def test_i8_golden_frame_layout(monkeypatch):
    """The exact blockscale bytes for a known vector: `u32 block,
    u32 n, f32 scales, int8 q` with a non-dividing length (the last
    block is short)."""
    monkeypatch.setenv('AUTODIST_QUANT_BLOCK', '8')
    x = np.array([0.0, 1.0, -2.0, 0.5, 4.0, -4.0, 0.25, 0.125,
                  10.0, -10.0], np.float32)   # 10 elems, blocks of 8
    raw = bytes(cc._encode(x, 'i8'))
    block, n = struct.unpack('<II', raw[:8])
    assert (block, n) == (8, 10)
    scales = np.frombuffer(raw, '<f4', count=2, offset=8)
    # per-block symmetric scale = maxabs/127 (+eps): block 0 maxabs=4,
    # block 1 maxabs=10
    np.testing.assert_allclose(scales, [4.0 / 127, 10.0 / 127],
                               rtol=1e-6)
    q = np.frombuffer(raw, np.int8, count=10, offset=16)
    assert q[1] == round(1.0 / (4.0 / 127))          # 32
    assert q[4] == 127 and q[5] == -127              # block maxima
    assert q[8] == 127 and q[9] == -127
    assert len(raw) == 8 + 2 * 4 + 10
    dec = cc._decode(raw, 'i8')
    assert dec.shape == (10,)
    # the max-magnitude element of each block round-trips near-exactly
    np.testing.assert_allclose(dec[[4, 5, 8, 9]], x[[4, 5, 8, 9]],
                               rtol=1e-5)
    # everything within the block's quantization step
    assert np.abs(dec - x).max() <= 10.0 / 127 / 2 + 1e-6


@pytest.mark.parametrize('n', [1, 7, 255, 256, 257, 1000])
def test_i8_roundtrip_nondividing_lengths(n):
    rng = np.random.RandomState(n)
    x = rng.randn(n).astype(np.float32)
    dec = cc._decode(bytes(cc._encode(x, 'i8')), 'i8')
    assert dec.shape == x.shape
    # worst-case error is half a quantization step of the hottest block
    step = np.abs(x).max() / 127
    assert np.abs(dec - x).max() <= step / 2 + 1e-6


def test_i8_decode_rejects_malformed_frames():
    with pytest.raises(ValueError):
        cc._decode(b'\x00' * 8, 'i8')          # block = 0
    good = bytes(cc._encode(np.ones(10, np.float32), 'i8'))
    with pytest.raises(ValueError):
        cc._decode(good[:-1], 'i8')            # truncated payload


def test_f32_and_bf16_roundtrip_goldens():
    x = np.array([1.0, -1.5, 3.14159265], np.float32)
    assert bytes(cc._encode(x, 'f32')) == x.tobytes()
    np.testing.assert_array_equal(cc._decode(x.tobytes(), 'f32'), x)
    # bf16 drops the low 16 mantissa bits with round-to-nearest-even
    dec = cc._decode(cc._encode(x, 'bf16'), 'bf16')
    import ml_dtypes
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(dec, want)


def test_wire_roundtrip_helpers_match_encode_decode(monkeypatch):
    """The session's error-feedback residual is exact ONLY if
    wire_roundtrip replicates the per-chunk frame layout bit-for-bit —
    including chunk boundaries that are not block multiples."""
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '700')  # odd boundary
    monkeypatch.setenv('AUTODIST_QUANT_BLOCK', '256')
    rng = np.random.RandomState(3)
    x = rng.randn(2000).astype(np.float32)
    want = np.concatenate([
        cc._decode(bytes(cc._encode(x[off:off + count], 'i8')), 'i8')
        for off, count in cc._chunk_ranges(x.size, 'i8')])
    np.testing.assert_array_equal(cc.wire_roundtrip(x, 'i8'), want)
    rows = rng.randn(40, 16).astype(np.float32)
    got = cc.rows_roundtrip(rows, 'i8')
    row_wire = 16 * cc._wire_itemsize('i8')
    want_rows = np.concatenate([
        cc._decode(bytes(cc._encode(rows[off:off + count], 'i8')),
                   'i8').reshape(count, -1)
        for off, count in cc._row_chunk_ranges(40, 4 + row_wire)])
    np.testing.assert_array_equal(got, want_rows)


def test_wire_nbytes_accounts_blockscale_overhead(monkeypatch):
    monkeypatch.setenv('AUTODIST_QUANT_BLOCK', '256')
    monkeypatch.delenv('AUTODIST_PS_CHUNK_BYTES', raising=False)
    n = 1000
    # 8-byte header + ceil(1000/256)=4 scales + 1000 int8
    assert cc.wire_nbytes(n, 'i8') == 8 + 4 * 4 + 1000
    assert cc.wire_nbytes(n, 'f32') == 4000
    assert cc.wire_nbytes(n, 'bf16') == 2000
    assert len(bytes(cc._encode(np.zeros(n, np.float32), 'i8'))) == \
        cc.wire_nbytes(n, 'i8')


def test_pull_wire_downgrades_i8_to_f32():
    """i8 is a push-direction format: pulls and authoritative stores
    must ride f32 under an i8 setting (quantizing at-rest state or
    reads would compound error with no residual to absorb it)."""
    assert cc._pull_wire('i8') == 'f32'
    assert cc._pull_wire('f32') == 'f32'
    assert cc._pull_wire('bf16') == 'bf16'
    with pytest.raises(ValueError):
        cc._wire_dtype('int8')


# -- golden frames through the native service ----------------------------

def _raw_bget(client, key, wire):
    """BGET at an explicit wire dtype, bypassing the client's
    pull-direction downgrade — exercises the service's encode_wire."""
    resp = client._rpc('BGET %s %s' % (key, wire))
    assert resp.startswith('VAL'), resp
    return client._read_exact(int(resp.split()[1]))


def test_service_decode_wire_i8_matches_python(coord):
    """BADD with an i8 payload must land EXACTLY the values the Python
    round-trip predicts (same float32 q*scale multiply on both sides) —
    the bit-exactness the session's residual carry rests on."""
    c = coord()
    rng = np.random.RandomState(0)
    x = rng.randn(1000).astype(np.float32)
    c.vset('qi8/t', np.zeros(1000, np.float32))
    c.vadd('qi8/t', x, wire='i8')
    np.testing.assert_array_equal(c.vget('qi8/t'),
                                  cc.wire_roundtrip(x, 'i8'))


def test_service_encode_wire_i8_bounded(coord):
    """The service-side i8 encoder (BGET reply path): decoded values
    stay within half a quantization step per block."""
    c = coord()
    rng = np.random.RandomState(1)
    x = rng.randn(777).astype(np.float32)   # non-dividing length
    c.vset('qi8/enc', x)
    dec = cc._decode(_raw_bget(c, 'qi8/enc', 'i8'), 'i8')
    step = np.abs(x).max() / 127
    assert np.abs(dec - x).max() <= step / 2 + 1e-6


def test_service_bf16_nan_and_rtne_edges(coord):
    """The C++ f32_to_bf16: NaN must quieten, not round into Inf, and
    ties must round to even — pinned against ml_dtypes' own cast."""
    import ml_dtypes
    c = coord()
    # 0x7f7fffff (max finite f32) rounds UP to bf16 Inf — that is
    # correct RTNE; a NaN (0x7fc00001, 0x7f800001) must stay NaN
    vals = np.array([np.nan, np.float32(3.0), np.float32(1.0),
                     np.frombuffer(struct.pack('<I', 0x3f803fff),
                                   np.float32)[0],    # tie-ish, down
                     np.frombuffer(struct.pack('<I', 0x3f808000),
                                   np.float32)[0],    # exact tie: even
                     np.frombuffer(struct.pack('<I', 0x3f818000),
                                   np.float32)[0],    # exact tie: up
                     np.float32(65535.0)], np.float32)
    c.vset('bf/t', vals)
    dec = cc._decode(_raw_bget(c, 'bf/t', 'bf16'), 'bf16')
    want = vals.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.isnan(dec[0]) and not np.isinf(dec[0])
    np.testing.assert_array_equal(dec[1:], want[1:])


def test_service_bsadd_i8_matches_rows_roundtrip(coord):
    """BSADD i8 framing (row_bytes = total blob length) scatter-adds
    exactly the rows the Python round-trip predicts, including
    repeated indices."""
    c = coord()
    rng = np.random.RandomState(2)
    rows = rng.randn(6, 33).astype(np.float32)
    idx = np.array([3, 7, 7, 20, 0, 49], np.int32)
    c.vset('qi8/tab', np.zeros((50, 33), np.float32))
    assert c.vsadd('qi8/tab', idx, rows, wire='i8') == 1
    want = np.zeros((50, 33), np.float32)
    for i, r in zip(idx, cc.rows_roundtrip(rows, 'i8')):
        want[i] += r
    np.testing.assert_array_equal(c.vget('qi8/tab', shape=(50, 33)),
                                  want)


def test_service_bsadd_i8_chunked(coord, monkeypatch):
    """Row-chunked i8 sparse pushes (several blockscale frames per
    logical push) apply exactly."""
    monkeypatch.setenv('AUTODIST_PS_CHUNK_BYTES', '256')
    c = coord()
    rng = np.random.RandomState(4)
    rows = rng.randn(20, 16).astype(np.float32)
    idx = np.arange(20, dtype=np.int32)
    c.vset('qi8/chtab', np.zeros((20, 16), np.float32))
    c.vsadd('qi8/chtab', idx, rows, wire='i8')
    np.testing.assert_array_equal(
        c.vget('qi8/chtab', shape=(20, 16)),
        cc.rows_roundtrip(rows, 'i8'))


def test_two_workers_accumulate_i8_pushes(coord):
    """2-worker loose-mode wire semantics: concurrent i8 pushes from
    two clients accumulate commutatively and EXACTLY (each push lands
    its own block round-trip; f32 accumulation at rest)."""
    c0 = coord()
    c0.vset('qi8/acc', np.zeros(512, np.float32))
    rng = np.random.RandomState(5)
    deltas = [rng.randn(512).astype(np.float32) for _ in range(4)]

    def worker(ds):
        cl = coord()
        for d in ds:
            cl.vadd('qi8/acc', d, wire='i8')
        cl.close()

    ts = [threading.Thread(target=worker, args=(deltas[:2],)),
          threading.Thread(target=worker, args=(deltas[2:],))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    want = np.zeros(512, np.float32)
    for d in deltas:
        want += cc.wire_roundtrip(d, 'i8')
    got = c0.vget('qi8/acc')
    # float32 adds commute only up to ordering; two orderings of four
    # addends differ at most by a few ULPs of the running sum
    np.testing.assert_allclose(got, want, atol=1e-4)


# -- end-to-end loose mode on the i8 wire --------------------------------

def _loose_sgd_run(port, wire, steps=5, dim=48, probe=None):
    """One fresh single-process loose-mode SGD run at the given wire
    dtype; returns (final W from the PS, ps_stats). ``probe(sess, ns)``
    runs after the first step for residual-carry assertions."""
    import autodist_tpu as ad
    from autodist_tpu.utils.loose_harness import single_process_loose_env
    saved = os.environ.get('AUTODIST_PS_WIRE_DTYPE')
    os.environ['AUTODIST_PS_WIRE_DTYPE'] = wire
    try:
        with single_process_loose_env(port, 1) as sees_one:
            autodist = ad.AutoDist(
                resource_info={'nodes': [
                    {'address': 'localhost', 'gpus': [0], 'chief': True,
                     'network_bandwidth': 100}]},
                strategy_builder=ad.strategy.PS(staleness=2))
            rng = np.random.RandomState(0)
            W0 = rng.randn(dim, dim).astype(np.float32)
            feed = rng.randn(8, dim).astype(np.float32)
            with autodist.scope():
                x = ad.placeholder(shape=[None, dim], dtype=np.float32,
                                   name='x')
                W = ad.Variable(W0, name='W')
                loss = ad.ops.reduce_mean(
                    ad.ops.square(ad.ops.matmul(x, W)))
                train_op = ad.optimizers.SGD(0.01).minimize(loss, [W])
                autodist._build()
                ns = autodist._transformed[0].id
                sees_one()
                sess = autodist.create_distributed_session()
                sess.run(train_op, {x: feed})
                if probe is not None:
                    probe(sess, ns, W0)
                for _ in range(steps - 1):
                    sess.run(train_op, {x: feed})
                w = sess.get_variable_value('W')
                stats = sess.ps_stats
                sess.close()
            return w, stats
    finally:
        if saved is None:
            os.environ.pop('AUTODIST_PS_WIRE_DTYPE', None)
        else:
            os.environ['AUTODIST_PS_WIRE_DTYPE'] = saved


@pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')
def test_loose_mode_i8_bounded_divergence_and_exact_residual(service):
    """End-to-end loose mode on the i8 push wire: (a) the PS state
    after the first push equals W0 + the delta's exact block
    round-trip, and the session's carried residual is exactly the mass
    the wire dropped; (b) after several steps the divergence vs the
    f32 wire stays bounded (error feedback), while pushes moved ~4x
    fewer bytes."""
    from autodist_tpu.runtime.coord_client import CoordClient
    port = service
    carried = {}

    def probe(sess, ns, W0):
        delta = np.asarray(sess._local_value('W'),
                           np.float32) - W0
        transmitted = cc.wire_roundtrip(delta, 'i8')
        residual = sess._push_residual['W']
        # the residual is EXACTLY what the wire dropped...
        np.testing.assert_array_equal(residual, delta - transmitted)
        assert np.abs(residual).max() > 0
        # ...and the service holds EXACTLY W0 + transmitted
        c = CoordClient(('127.0.0.1', port))
        np.testing.assert_array_equal(
            c.vget('%s/var/W' % ns, shape=W0.shape), W0 + transmitted)
        c.close()
        carried['ok'] = True

    w8, s8 = _loose_sgd_run(port, 'i8', probe=probe)
    w32, s32 = _loose_sgd_run(port, 'f32')
    assert carried.get('ok')
    assert float(np.abs(w32 - w8).max()) < 0.01
    assert s32['push_bytes'] / s8['push_bytes'] >= 3.0
    # pulls stayed f32: byte parity in the read direction
    assert s32['pull_bytes'] == s8['pull_bytes']


# -- bucketed int8 sync (the compressor/plan tentpole) -------------------

def _eight_device_mesh():
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip('needs 8 devices (CPU mesh)')
    from autodist_tpu.const import AXIS_DATA
    return Mesh(np.asarray(devs[:8]), (AXIS_DATA,))


def test_int8_bucket_fusion_and_per_member_residuals():
    """Same-group f32 Int8RingCompressor grads fuse into byte-capped
    buckets (one quantized collective per bucket) with each member's
    error-feedback residual carried separately in aux-state."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from autodist_tpu.frontend import graph as fe
    from autodist_tpu.parallel.axes import shard_map
    from autodist_tpu.parallel.plan import ExecutionPlan, ShardedGrad
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.adapter import (FunctionalModel,
                                               PytreeGraphItem)

    mesh = _eight_device_mesh()
    n_vars, dim = 6, 64

    def init_fn(rng):
        return {'v%02d' % i: jnp.zeros((dim, dim), jnp.float32)
                for i in range(n_vars)}

    gi = PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))
    rs = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(8)), 'network_bandwidth': 100}]})
    strategy = AllReduce(chunk_size=2,
                         compressor='Int8RingCompressor').build(gi, rs)
    plan = ExecutionPlan(strategy, gi, mesh)
    sources = list(gi.trainable_var_op_to_var.values())
    rng = np.random.RandomState(0)
    grads = [jnp.asarray(rng.rand(dim, dim).astype('f4'))
             for _ in sources]
    aux = {'compressor/%s' % v.name:
           {'residual': jnp.zeros((dim, dim), jnp.float32)}
           for v in sources}

    def sync(*gs):
        env = fe.Env({}, {}, aux_state=aux)
        out = plan.sync_gradients(sources, list(gs), env)
        outs = tuple(o.value if isinstance(o, ShardedGrad) else o
                     for o in out)
        res = tuple(env.aux_updates['compressor/%s' % v.name]['residual']
                    for v in sources)
        return outs, res

    f = jax.jit(shard_map(
        sync, mesh, tuple(P() for _ in grads),
        (tuple(P() for _ in grads), tuple(P() for _ in grads))))
    outs, res = f(*grads)
    # fused: 6 vars over chunk_size=2 -> 3 int8 buckets of 2
    stats = plan.last_bucket_stats
    assert [b['compressor'] for b in stats] == \
        ['Int8RingCompressor'] * 3
    assert all(b['vars'] == 2 for b in stats)
    # all replicas fed the same grad -> the mean is the grad itself,
    # up to bounded quantization error
    for o, g in zip(outs, grads):
        assert float(jnp.max(jnp.abs(o - g))) < 0.05
    # one residual per member, member-shaped, live
    assert all(r.shape == (dim, dim) for r in res)
    assert all(float(jnp.abs(r).max()) > 0 for r in res)
    # residual = (grad + 0) - block_roundtrip(bucket slice): verify one
    # member against the bucket-level quantization
    from autodist_tpu.parallel.compressor import block_roundtrip
    b0 = stats[-1]   # emitted tail-first; members map via 'members'
    names = [v.name for v in sources]
    i0, i1 = (names.index(m) for m in b0['members'])
    buf = jnp.concatenate([grads[i0].reshape(-1),
                           grads[i1].reshape(-1)])
    rt = block_roundtrip(buf)
    want0 = (grads[i0].reshape(-1) - rt[:dim * dim]).reshape(dim, dim)
    np.testing.assert_allclose(np.asarray(res[i0]), np.asarray(want0),
                               atol=1e-7)


def test_int8_bucket_outlier_contained_to_one_block():
    """EQuARX's point: per-block scales bound an outlier's quantization
    damage to its own block instead of the whole bucket."""
    import jax.numpy as jnp

    from autodist_tpu.parallel.compressor import (block_roundtrip,
                                                  quant_block_size)
    rng = np.random.RandomState(0)
    y = rng.randn(4096).astype('f4')
    y[100] = 1e4   # one outlier in block 0
    rt = np.asarray(block_roundtrip(jnp.asarray(y)))
    err = np.abs(rt - y)
    blk = quant_block_size()
    # other blocks keep their own fine scale (~|x|max/127 step); a
    # per-TENSOR scale would spread ~1e4/127 error everywhere
    assert err[blk:].max() < 0.05
    assert err[:blk].max() > 1.0   # the outlier block pays, alone


def test_int8_static_schedule_mirrors_fusion():
    """The simulator prices the SAME bucket layout the plan emits:
    static_collective_schedule fuses Int8RingCompressor f32 groups."""
    import jax.numpy as jnp

    from autodist_tpu.parallel.plan import static_collective_schedule
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.adapter import (FunctionalModel,
                                               PytreeGraphItem)

    def init_fn(rng):
        return {'v%02d' % i: jnp.zeros((64, 64), jnp.float32)
                for i in range(6)}

    gi = PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))
    rs = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(8)), 'network_bandwidth': 100}]})
    strategy = AllReduce(chunk_size=2,
                         compressor='Int8RingCompressor').build(gi, rs)
    sched = static_collective_schedule(strategy, gi, 8)
    ars = [e for e in sched if e['kind'] == 'all_reduce']
    assert [e['compressor'] for e in ars] == \
        ['Int8RingCompressor'] * 3
    assert all(e['vars'] == 2 for e in ars)


def test_int8_fusion_excludes_small_and_non_f32_members():
    """Sub-MIN_SIZE (and non-f32) tensors have no error-feedback
    residual, so they must keep the plain lossless collective instead
    of riding a quantized bucket uncompensated — the shared predicate
    both the runtime and the static schedule use."""
    import jax.numpy as jnp

    from autodist_tpu.parallel import compressor as comp
    from autodist_tpu.parallel.plan import static_collective_schedule
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.adapter import (FunctionalModel,
                                               PytreeGraphItem)

    c = comp.Int8RingCompressor('v')
    assert comp.int8_bucket_fusable(c, np.float32, 256)
    assert not comp.int8_bucket_fusable(c, np.float32, 4)   # < MIN_SIZE
    assert not comp.int8_bucket_fusable(c, np.float16, 256)
    assert not comp.int8_bucket_fusable(comp.NoneCompressor('v'),
                                        np.float32, 256)

    def init_fn(rng):
        return {'big0': jnp.zeros((64, 64), jnp.float32),
                'big1': jnp.zeros((64, 64), jnp.float32),
                'tiny': jnp.zeros((4,), jnp.float32)}

    gi = PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))
    rs = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(8)), 'network_bandwidth': 100}]})
    strategy = AllReduce(chunk_size=2,
                         compressor='Int8RingCompressor').build(gi, rs)
    sched = static_collective_schedule(strategy, gi, 8)
    by_members = {tuple(e['members']): e for e in sched}
    fused = by_members[('big1', 'big0')] if ('big1', 'big0') in \
        by_members else by_members[('big0', 'big1')]
    assert fused['vars'] == 2
    assert by_members[('tiny',)]['vars'] == 1   # excluded from fusion


def test_service_bsadd_i8_rejects_empty_blob(coord):
    """An i8 BSADD whose blockscale blob decodes to zero elements with
    nrows > 0 must be rejected (ncols would be 0 — the shape-check
    modulo would SIGFPE the whole service)."""
    import struct
    c = coord()
    c.vset('qi8/empty', np.zeros((4, 4), np.float32))
    idx = np.arange(2, dtype=np.int32)
    blob = struct.pack('<II', 256, 0)   # block=256, n=0: empty payload
    resp = c._rpc('BSADD %s 2 %d i8' % ('qi8/empty', len(blob)),
                  [memoryview(idx).cast('B'), blob])
    assert resp.startswith('ERR'), resp
    c.ping()   # the service survived


def test_compressor_ef_init_state_skips_non_f32():
    """Residual allocation for variables whose reduce() falls through
    to the plain collective is wasted HBM (and the simulator's memory
    estimate counts it)."""
    from autodist_tpu.parallel.compressor import (HorovodCompressorEF,
                                                  Int8RingCompressor)
    assert HorovodCompressorEF('v').init_state(
        np.zeros((256, 4), np.float16)) == {}
    assert Int8RingCompressor('v').init_state(
        np.zeros((256, 4), np.float16)) == {}
    assert 'residual' in HorovodCompressorEF('v').init_state(
        np.zeros((256, 4), np.float32))
    assert 'residual' in Int8RingCompressor('v').init_state(
        np.zeros((256, 4), np.float32))


def test_cost_model_reranks_int8_by_bandwidth():
    """The acceptance re-rank: under a bandwidth-constrained link the
    int8 tier wins; on a bandwidth-rich link its quantize cost loses —
    the cost model actually orders the tiers differently."""
    from autodist_tpu.models.rnn import LSTMLM
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.simulator import cost_model, search
    from autodist_tpu.strategy.adapter import PytreeGraphItem

    gi = PytreeGraphItem(LSTMLM(vocab=2000, dim=64, hidden=128,
                                n_layers=1))
    rs = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(8)), 'network_bandwidth': 100}]})
    cands = [c for c in search.default_candidates()
             if c[0] in ('AllReduce(chunk=128)', 'AllReduce(int8-wire)')]

    def winner(beta):
        params = cost_model.CostModelParams(beta_ici_s_per_byte=beta)
        feas, _ = search.rank(gi, rs, candidates=cands, params=params,
                              num_replicas=8)
        return feas[0].name

    assert winner(8e-9) == 'AllReduce(int8-wire)'      # DCN-bound
    assert winner(1e-12) == 'AllReduce(chunk=128)'     # wire ~free


def test_wire_bytes_prices_scale_overhead(monkeypatch):
    monkeypatch.setenv('AUTODIST_QUANT_BLOCK', '256')
    from autodist_tpu.simulator.cost_model import wire_bytes
    nbytes = 1024 * 4   # 1024 f32 elements
    assert wire_bytes(nbytes, 'float32', 'Int8RingCompressor') == \
        1024 + 4 * 4   # int8 payload + 4 block scales
    assert wire_bytes(nbytes, 'float32', 'HorovodCompressor') == 2048
    assert wire_bytes(nbytes, 'float32', 'PowerSGDCompressor') == nbytes
    assert wire_bytes(nbytes, 'float32', None) == nbytes


def test_bucket_report_routes_wire_bytes():
    """profiling.bucket_report reports the WIRE, not just raw tensor
    bytes — the 4x win must be visible in the report that motivates
    it."""
    from autodist_tpu.utils.profiling import bucket_report

    class FakePlan:
        last_bucket_stats = [
            {'kind': 'all_reduce', 'compressor': 'Int8RingCompressor',
             'dtype': 'float32', 'bytes': 1024 * 4, 'vars': 2},
            {'kind': 'all_reduce', 'compressor': None,
             'dtype': 'float32', 'bytes': 4096, 'vars': 1},
        ]

    rep = bucket_report(FakePlan())
    assert rep['total_bytes'] == 8192
    assert rep['buckets'][0]['wire_bytes'] < 8192 // 4
    assert rep['buckets'][1]['wire_bytes'] == 4096
    assert rep['total_wire_bytes'] == sum(
        b['wire_bytes'] for b in rep['buckets'])
