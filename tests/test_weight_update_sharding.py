"""Cross-replica weight-update sharding (ISSUE 14, arXiv:2004.13336).

Covers the acceptance surface: sharded-vs-replicated bit-comparability
(variables AND optimizer slots, f32 within re-association ulps —
bit-identical on exactly-representable sums), uneven/padded flat
shapes, buffer donation, the hierarchical two-level treatment of the
ZeRO scatter/gather halves (static==traced), the shared
choose_update_sharding decision, layout-aware memory estimates, and
the AutoStrategy rank flip on a memory-tight budget.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import autodist_tpu as ad
from autodist_tpu import autodist as ad_mod
from autodist_tpu.const import AXIS_DATA
from autodist_tpu.frontend import graph as fe
from autodist_tpu.parallel.axes import shard_map
from autodist_tpu.parallel.plan import (ExecutionPlan, ShardedGrad,
                                        UpdateShard,
                                        hierarchical_all_gather,
                                        hierarchical_psum_scatter,
                                        static_collective_schedule)
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.simulator.cost_model import (CostModelParams,
                                               choose_update_sharding,
                                               memory_footprint,
                                               optimizer_slot_count,
                                               predict)
from autodist_tpu.strategy import AllReduce, AutoStrategy, PartitionedPS
from autodist_tpu.strategy.adapter import FunctionalModel, PytreeGraphItem

MiB = 1 << 20

RESOURCE_INFO = {'nodes': [{'address': 'localhost',
                            'gpus': list(range(8)),
                            'chief': True,
                            'network_bandwidth': 100}]}


def _make_gi(shapes):
    def init_fn(rng):
        return {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    return PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))


def _make_rs(n=8):
    return ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(n)), 'network_bandwidth': 100}]})


def _train(builder, optimizer_fn, shapes, steps=3, seed=0,
           integral=False):
    """Run a small DSL model end-to-end; returns (var values,
    flattened slot leaves by var, plan, session is closed)."""
    ad_mod._DEFAULT_AUTODIST.clear()
    autodist = ad.AutoDist(resource_info=RESOURCE_INFO,
                           strategy_builder=builder)
    rng = np.random.RandomState(seed)
    din = shapes['W'][0]
    if integral:
        # exactly-representable inputs: small integers keep every
        # partial sum exact in f32, so replicated-vs-sharded must be
        # BIT-identical (psum vs psum_scatter is pure re-association)
        xs = rng.randint(-3, 4, size=(64, din)).astype(np.float32)
        ys = rng.randint(-3, 4, size=(64,)).astype(np.float32)
    else:
        xs = rng.randn(64, din).astype(np.float32)
        ys = rng.randn(64).astype(np.float32)
    with autodist.scope():
        variables = {}
        for name, shape in shapes.items():
            init = rng.randint(-2, 3, size=shape).astype(np.float32) \
                if integral else rng.randn(*shape).astype(np.float32)
            variables[name] = ad.Variable(init, name=name)
        x = ad.placeholder(shape=[None, din], dtype=np.float32,
                           name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        h = ad.ops.matmul(x, variables['W'])
        hidden = shapes['W'][1]
        pred = ad.ops.squeeze(
            ad.ops.matmul(h, ad.ops.reshape(variables['V'],
                                            (hidden, 1))), axis=1)
        if 'b' in variables:
            pred = pred + ad.ops.reduce_sum(variables['b'])
        loss = ad.ops.reduce_mean(ad.ops.square(pred - y))
        opt = optimizer_fn()
        train = opt.minimize(loss)
        sess = autodist.create_distributed_session()
        for _ in range(steps):
            sess.run(train, feed_dict={x: xs, y: ys})
        values = {name: np.asarray(sess.run(v.read()))
                  for name, v in variables.items()}
        plan = sess._plan
        slots = {}
        n = plan.num_replicas
        for uid, by_var in sess._opt_state.items():
            for vname, state in by_var.items():
                flat = []
                for leaf in jax.tree.leaves(state):
                    arr = np.asarray(leaf)
                    vp = plan.var_plans[vname]
                    if vp.update_sharded and \
                            arr.shape == (vp.wus_padded,):
                        size = int(np.prod(vp.var.shape or (1,)))
                        arr = arr[:size].reshape(vp.var.shape)
                    flat.append(arr)
                slots[vname] = flat
    return values, slots, plan


SHAPES = {'W': (4, 6), 'V': (6,), 'b': (3,)}


def test_sharded_update_bit_identical_on_representable_sums():
    """The tentpole's numerics contract: with exactly-representable
    gradients (integral data, one step — every partial sum exact in
    f32, so psum vs psum_scatter is pure re-association of exact
    values) the sharded update (reduce-scatter + shard-local Adam +
    all-gather) is BIT-identical to the replicated baseline —
    variables AND slot state."""
    base_v, base_s, _ = _train(AllReduce(),
                               lambda: ad.optimizers.Adam(0.05),
                               SHAPES, steps=1, integral=True)
    wus_v, wus_s, plan = _train(
        AllReduce(weight_update_sharding='always'),
        lambda: ad.optimizers.Adam(0.05), SHAPES, steps=1,
        integral=True)
    assert any(p.update_sharded for p in plan.var_plans.values())
    for name in SHAPES:
        assert np.array_equal(base_v[name], wus_v[name]), name
        for a, b in zip(base_s[name], wus_s[name]):
            assert np.array_equal(a, b), 'slot drift on %s' % name


def test_sharded_update_within_ulps_random_data():
    """Random (non-representable) gradients: replicated vs sharded
    stays within f32 re-association tolerance, slots included."""
    base_v, base_s, _ = _train(AllReduce(),
                               lambda: ad.optimizers.Adam(0.05),
                               SHAPES, steps=4)
    wus_v, wus_s, _ = _train(
        AllReduce(weight_update_sharding='always'),
        lambda: ad.optimizers.Adam(0.05), SHAPES, steps=4)
    for name in SHAPES:
        np.testing.assert_allclose(base_v[name], wus_v[name],
                                   rtol=1e-6, atol=1e-6)
        for a, b in zip(base_s[name], wus_s[name]):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_uneven_padded_shard_shapes():
    """Flat sizes that do not divide the 8-way mesh (35, 7, 3 -> pads
    of 5/1/5) must still match the replicated baseline exactly on
    representable sums — the zero-padded tail never leaks into real
    elements."""
    shapes = {'W': (5, 7), 'V': (7,), 'b': (3,)}
    base_v, _, _ = _train(AllReduce(),
                          lambda: ad.optimizers.Adam(0.05),
                          shapes, steps=1, integral=True)
    wus_v, _, plan = _train(
        AllReduce(weight_update_sharding='always'),
        lambda: ad.optimizers.Adam(0.05), shapes, steps=1,
        integral=True)
    pads = {n: p.wus_pad for n, p in plan.var_plans.items()}
    assert pads['W'] == 5 and pads['V'] == 1 and pads['b'] == 5
    for name in shapes:
        assert np.array_equal(base_v[name], wus_v[name]), name


def test_lamb_fused_shard_update_matches_replicated():
    """LAMB's trust ratio couples elements; the fused shard update
    psums the norms, so sharded matches replicated within
    re-association ulps (never shard-local norms)."""
    base_v, _, _ = _train(
        AllReduce(),
        lambda: ad.optimizers.LAMB(0.05, weight_decay=0.01),
        SHAPES, steps=4)
    wus_v, _, _ = _train(
        AllReduce(weight_update_sharding='always'),
        lambda: ad.optimizers.LAMB(0.05, weight_decay=0.01),
        SHAPES, steps=4)
    for name in SHAPES:
        np.testing.assert_allclose(base_v[name], wus_v[name],
                                   rtol=1e-5, atol=1e-6)


def test_slots_stored_as_flat_shards():
    """The memory claim made real: each update-sharded variable's
    non-scalar slot leaves are GLOBAL (wus_padded,) arrays sharded
    over the data axis — per-device slot bytes drop to 1/n."""
    ad_mod._DEFAULT_AUTODIST.clear()
    autodist = ad.AutoDist(resource_info=RESOURCE_INFO,
                           strategy_builder=AllReduce(
                               weight_update_sharding='always'))
    rng = np.random.RandomState(0)
    xs = rng.randn(64, 4).astype(np.float32)
    ys = rng.randn(64).astype(np.float32)
    with autodist.scope():
        W = ad.Variable(rng.randn(4, 6).astype(np.float32), name='W')
        V = ad.Variable(rng.randn(6).astype(np.float32), name='V')
        x = ad.placeholder(shape=[None, 4], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        pred = ad.ops.squeeze(
            ad.ops.matmul(ad.ops.matmul(x, W),
                          ad.ops.reshape(V, (6, 1))), axis=1)
        loss = ad.ops.reduce_mean(ad.ops.square(pred - y))
        train = ad.optimizers.Adam(0.05).minimize(loss)
        sess = autodist.create_distributed_session()
        sess.run(train, feed_dict={x: xs, y: ys})
        plan = sess._plan
        n = plan.num_replicas
        checked = 0
        for uid, by_var in sess._opt_state.items():
            for vname, state in by_var.items():
                vp = plan.var_plans[vname]
                assert vp.update_sharded
                for leaf in jax.tree.leaves(state):
                    if getattr(leaf, 'ndim', 0) == 0:
                        continue   # step count: replicated scalar
                    assert tuple(leaf.shape) == (vp.wus_padded,)
                    specs = set()
                    for sh in leaf.addressable_shards:
                        specs.add(sh.data.shape)
                    # each device holds exactly the 1/n flat shard
                    assert specs == {(vp.wus_padded // n,)}
                    checked += 1
        assert checked >= 4   # mu+nu for both vars


def test_donation_reuses_buffers():
    """The jitted step donates var/opt state; on backends that honor
    donation the pre-step slot buffers must be deleted after the run
    (the sharded update reuses them in place)."""
    probe = jax.jit(lambda a: a + 1, donate_argnums=0)
    x = jnp.zeros((128,), jnp.float32)
    probe(x)
    if not x.is_deleted():
        pytest.skip('backend does not honor buffer donation')
    ad_mod._DEFAULT_AUTODIST.clear()
    autodist = ad.AutoDist(resource_info=RESOURCE_INFO,
                           strategy_builder=AllReduce(
                               weight_update_sharding='always'))
    rng = np.random.RandomState(0)
    xs = rng.randn(64, 4).astype(np.float32)
    ys = rng.randn(64).astype(np.float32)
    with autodist.scope():
        W = ad.Variable(rng.randn(4, 6).astype(np.float32), name='W')
        V = ad.Variable(rng.randn(6).astype(np.float32), name='V')
        x = ad.placeholder(shape=[None, 4], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        pred = ad.ops.squeeze(
            ad.ops.matmul(ad.ops.matmul(x, W),
                          ad.ops.reshape(V, (6, 1))), axis=1)
        loss = ad.ops.reduce_mean(ad.ops.square(pred - y))
        train = ad.optimizers.Adam(0.05).minimize(loss)
        sess = autodist.create_distributed_session()
        sess.run(train, feed_dict={x: xs, y: ys})   # compile + run
        before = [leaf for by_var in sess._opt_state.values()
                  for state in by_var.values()
                  for leaf in jax.tree.leaves(state)
                  if getattr(leaf, 'ndim', 0)]
        sess.run(train, feed_dict={x: xs, y: ys})
        deleted = [leaf.is_deleted() for leaf in before]
        assert all(deleted), 'donated slot buffers were copied, ' \
            'not reused (%d/%d deleted)' % (sum(deleted), len(deleted))


# -- the shared decision --------------------------------------------------

def test_choose_update_sharding_gating():
    params = CostModelParams()
    # never / single replica / compressed wire never shard
    assert not choose_update_sharding(1 * MiB, 'float32',
                                      'NoneCompressor', 8, params,
                                      knob='never')
    assert not choose_update_sharding(1 * MiB, 'float32',
                                      'NoneCompressor', 1, params,
                                      knob='always')
    assert not choose_update_sharding(1 * MiB, 'float32',
                                      'Int8RingCompressor', 8, params,
                                      knob='always')
    assert choose_update_sharding(1 * MiB, 'float32',
                                  'NoneCompressor', 8, params,
                                  knob='always')
    # auto: ICI-rich (cheap wire, HBM-bound) shards, DCN-bound keeps
    # the replicated update — the freed-memory-vs-exposure trade
    assert choose_update_sharding(4 * MiB, 'float32',
                                  'NoneCompressor', 8, params,
                                  knob='auto', opt_slots=2,
                                  cross_node=False)
    assert not choose_update_sharding(4 * MiB, 'float32',
                                      'NoneCompressor', 8, params,
                                      knob='auto', opt_slots=2,
                                      cross_node=True)
    # no slots to free -> nothing to buy with the exposed gather
    assert not choose_update_sharding(4 * MiB, 'float32',
                                      'NoneCompressor', 8, params,
                                      knob='auto', opt_slots=0)
    # a forced RING spec is an explicit flat-ring request: the RS/AG
    # pair would drop the forced ppermute emission, so replicated
    # stays even under knob='always'
    assert not choose_update_sharding(1 * MiB, 'float32',
                                      'NoneCompressor', 8, params,
                                      knob='always', spec='RING')
    # 'ineligible' (sparse-read / row-lazy vars, set by VarPlan) never
    # shards
    assert not choose_update_sharding(1 * MiB, 'float32',
                                      'NoneCompressor', 8, params,
                                      knob='ineligible')


def test_ring_spec_keeps_replicated_update():
    gi = _make_gi({'w': (1024, 1024)})
    rs = _make_rs(8)
    s = AllReduce(all_reduce_spec='RING',
                  weight_update_sharding='always').build(gi, rs)
    sched = static_collective_schedule(s, gi, 8)
    assert not any(e['wus'] for e in sched)


def test_sparse_read_vars_stay_replicated(monkeypatch):
    """Row-lazy (sparse-read) variables are INELIGIBLE for update
    sharding — the flat 1/n shard layout cannot preserve
    LazyAdam/LazyMomentum zero-grad-row semantics — and not even the
    env override shards them; dense peers in the same strategy still
    shard."""
    gi = _make_gi({'emb': (64, 16), 'w': (64, 16)})
    for var in gi.trainable_var_op_to_var.values():
        if var.name == 'emb':
            var.sparse_read = True
    rs = _make_rs(8)
    s = AllReduce(chunk_size=2,
                  weight_update_sharding='always').build(gi, rs)
    sched = static_collective_schedule(s, gi, 8)
    wus_members = {m for e in sched if e['wus'] for m in e['members']}
    assert 'w' in wus_members and 'emb' not in wus_members
    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    plan = ExecutionPlan(s, gi, mesh)
    assert plan.var_plans['w'].update_sharded
    assert not plan.var_plans['emb'].update_sharded
    assert plan.var_plans['emb'].weight_update_sharding == 'ineligible'
    # the env override flips dense 'never' vars but not ineligible ones
    monkeypatch.setenv('AUTODIST_WEIGHT_UPDATE_SHARDING', 'always')
    s2 = AllReduce(chunk_size=2).build(gi, rs)
    sched2 = static_collective_schedule(s2, gi, 8)
    wus2 = {m for e in sched2 if e['wus'] for m in e['members']}
    assert 'w' in wus2 and 'emb' not in wus2


def test_env_knob_overrides_and_validates(monkeypatch):
    params = CostModelParams()
    monkeypatch.setenv('AUTODIST_WEIGHT_UPDATE_SHARDING', 'always')
    assert choose_update_sharding(1 * MiB, 'float32',
                                  'NoneCompressor', 8, params,
                                  knob='never')
    monkeypatch.setenv('AUTODIST_WEIGHT_UPDATE_SHARDING', 'never')
    assert not choose_update_sharding(1 * MiB, 'float32',
                                      'NoneCompressor', 8, params,
                                      knob='always')
    monkeypatch.setenv('AUTODIST_WEIGHT_UPDATE_SHARDING', 'bogus')
    from autodist_tpu.const import ENV
    with pytest.raises(ValueError):
        ENV.AUTODIST_WEIGHT_UPDATE_SHARDING.val


def test_optimizer_slot_count_from_capture():
    ad_mod._DEFAULT_AUTODIST.clear()
    g = fe.Graph()
    with g.as_default():
        v = ad.Variable(np.zeros(4, np.float32), name='v')
        x = ad.placeholder(shape=[4], dtype=np.float32, name='x')
        loss = ad.ops.reduce_sum(ad.ops.square(v - x))
        opt = ad.optimizers.SGD(0.1)   # momentum 0 -> no slots
        opt.minimize(loss)

    class GI:
        graph = g
    assert optimizer_slot_count(GI()) == 0
    with g.as_default():
        ad.optimizers.Adam(0.1)
    assert optimizer_slot_count(GI()) == 2
    # pytree graph items have no capture: conservative default
    assert optimizer_slot_count(_make_gi({'w': (4,)})) == 2


# -- static schedule + memory ---------------------------------------------

def test_static_schedule_emits_wus_pair_and_memory_drops_slots():
    gi = _make_gi({'w': (1024, 1024)})
    rs = _make_rs(8)
    s = AllReduce(weight_update_sharding='always').build(gi, rs)
    sched = static_collective_schedule(s, gi, 8)
    kinds = [(e['kind'], e['phase'], e['wus']) for e in sched]
    assert ('psum_scatter', 'grad', True) in kinds
    assert ('all_gather', 'param', True) in kinds
    assert len(sched) == 2
    # both halves carry the padded bucket bytes
    assert sched[0]['bytes'] == sched[1]['bytes'] == 4 * MiB
    mem = memory_footprint(s, gi, 8, optimizer_slots=2,
                           schedule=sched)
    # slots sharded to 1/n; the replicated baseline keeps them full
    base = AllReduce().build(gi, rs)
    mem_base = memory_footprint(base, gi, 8, optimizer_slots=2)
    assert mem_base['optimizer_bytes'] == 8 * MiB
    assert mem['optimizer_bytes'] == 1 * MiB
    assert mem['grads_bytes'] == mem_base['grads_bytes'] // 8


def test_wus_static_matches_traced():
    """The static==traced pin for the new emissions: kind/bytes/
    members/hier of the wus reduce-scatter AND the bucketed param
    all-gather agree between static_collective_schedule and the traced
    last_bucket_stats."""
    shapes = {'v%02d' % i: (64, 64) for i in range(4)}
    gi = _make_gi(shapes)
    rs = _make_rs(8)
    strategy = AllReduce(chunk_size=2,
                         weight_update_sharding='always').build(gi, rs)
    static = [e for e in static_collective_schedule(strategy, gi, 8)
              if e['wus']]

    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    plan = ExecutionPlan(strategy, gi, mesh)
    sources = list(gi.trainable_var_op_to_var.values())
    grads = [jnp.ones(s, jnp.float32) for s in shapes.values()]

    def sync(*gs):
        out = plan.sync_gradients(sources, list(gs), fe.Env({}, {}))
        # run the gather half too so its emission is recorded
        gathered = plan.gather_updated_params(
            {sh.var.name: sh for sh in out
             if isinstance(sh, UpdateShard)})
        return tuple(gathered[s.name] for s in sources)

    f = shard_map(sync, mesh, tuple(P() for _ in grads),
                         tuple(P() for _ in grads))
    jax.eval_shape(f, *grads)
    traced = [e for e in plan.last_bucket_stats if e.get('wus')]

    def key(e):
        return (e['kind'], e['bytes'], tuple(e['members']),
                e.get('hier', 0))
    assert sorted(map(key, static)) == sorted(map(key, traced))
    # and the traced scatter count equals the traced gather count
    assert sum(1 for e in traced if e['kind'] == 'psum_scatter') == \
        sum(1 for e in traced if e['kind'] == 'all_gather')


def test_predict_prices_wus_param_gather_exposed():
    gi = _make_gi({'w': (1024, 1024)})
    rs = _make_rs(8)
    s = AllReduce(weight_update_sharding='always').build(gi, rs)
    rep = predict(s, gi, rs, num_replicas=8, optimizer_slots=2)
    by_kind = {b['kind']: b for b in rep.breakdown}
    assert by_kind['psum_scatter']['wus']
    assert by_kind['all_gather']['wus']
    # RS + AG together price like the all-reduce they replace
    base = AllReduce().build(gi, rs)
    rep_base = predict(base, gi, rs, num_replicas=8,
                       optimizer_slots=2)
    assert rep.sync_time_s == pytest.approx(rep_base.sync_time_s,
                                            rel=1e-9)
    # but the param gather is fully exposed while a lone AR bucket is
    # also unhidden -> exposed time equal here; memory is the win
    assert rep.predicted_peak_bytes < rep_base.predicted_peak_bytes


def test_predict_wus_reduce_scatter_keeps_overlap_haircut():
    """The wus reduce-scatter replaces an AR bucket in the same
    backward position, so predict() gives every non-last grad-phase RS
    the same overlap haircut AR buckets get (the exposure model
    choose_update_sharding assumes: only the param gather is newly
    exposed), while every wus param all-gather is priced fully
    exposed."""
    gi = _make_gi({'v%d' % i: (1024, 1024) for i in range(4)})
    rs = _make_rs(8)
    s = AllReduce(chunk_size=2,
                  weight_update_sharding='always').build(gi, rs)
    rep = predict(s, gi, rs, num_replicas=8, optimizer_slots=2)
    rss = [b for b in rep.breakdown
           if b['kind'] == 'psum_scatter' and b['wus']]
    ags = [b for b in rep.breakdown
           if b['kind'] == 'all_gather' and b['wus']]
    assert len(rss) > 1 and len(ags) == len(rss)
    params = CostModelParams()
    for b in rss[:-1]:
        assert b['exposed_time_s'] == pytest.approx(
            b['time_s'] * (1.0 - params.overlap_discount))
    assert rss[-1]['exposed_time_s'] == pytest.approx(rss[-1]['time_s'])
    for b in ags:
        assert b['exposed_time_s'] == pytest.approx(b['time_s'])


# -- hierarchical ZeRO halves ---------------------------------------------

def test_hierarchical_halves_bit_identical_and_pinned(monkeypatch):
    """The ZeRO scatter/gather halves' two-level treatment: the
    permuted hierarchical halves deliver the SAME chunk ownership as
    the flat collectives (bit-identical on representable sums), and
    static==traced agree on which emissions go two-level."""
    monkeypatch.setenv('AUTODIST_HIERARCHY_NODES', '2')
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), (AXIS_DATA,))
    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    x = jnp.arange(64, dtype=jnp.float32)

    def two_level(v):
        s = hierarchical_psum_scatter(v, AXIS_DATA, groups)
        return s, hierarchical_all_gather(s, AXIS_DATA, groups)

    def flat(v):
        s = jax.lax.psum_scatter(v, AXIS_DATA, scatter_dimension=0,
                                 tiled=True)
        return s, jax.lax.all_gather(s, AXIS_DATA, tiled=True)

    fh = shard_map(two_level, mesh, (P(),), (P(AXIS_DATA), P()))
    ff = shard_map(flat, mesh, (P(),), (P(AXIS_DATA), P()))
    sh, ah = fh(x)
    sf, af = ff(x)
    assert jnp.array_equal(sh, sf)   # same ownership, same values
    assert jnp.array_equal(ah, af)

    # static==traced for a ZeRO (PartitionedPS) strategy
    shapes = {'w': (512, 64), 'b': (64,)}
    gi = _make_gi(shapes)
    strategy = PartitionedPS().build(gi, _make_rs(8))
    static = static_collective_schedule(strategy, gi, 8, nodes=2)
    scatters = [e for e in static if e['kind'] == 'psum_scatter']
    gathers = [e for e in static if e['kind'] == 'all_gather']
    assert scatters and gathers
    assert all(e['hier'] == 2 for e in scatters + gathers)

    plan = ExecutionPlan(strategy, gi, mesh)
    assert plan.hier_groups == groups
    sources = list(gi.trainable_var_op_to_var.values())
    grads = [jnp.ones(s, jnp.float32) for s in shapes.values()]

    def sync(*gs):
        out = plan.sync_gradients(sources, list(gs), fe.Env({}, {}))
        return tuple(o.gather() if isinstance(o, ShardedGrad) else o
                     for o in out)

    f = shard_map(sync, mesh, tuple(P() for _ in grads),
                         tuple(P() for _ in grads))
    outs = f(*grads)
    traced = [(e['kind'], e['bytes'], e.get('hier'))
              for e in plan.last_bucket_stats]
    assert sorted(traced) == sorted(
        [(e['kind'], e['bytes'], e['hier']) for e in scatters])
    # mean of ones over 8 replicas gathers back to exactly ones
    for o, g in zip(outs, grads):
        assert jnp.array_equal(o, jnp.ones_like(g))


def test_zero_gather_hier_decision_respects_knob():
    shapes = {'w': (512, 64)}
    gi = _make_gi(shapes)
    from autodist_tpu.strategy.base import PSSynchronizer
    strategy = PartitionedPS().build(gi, _make_rs(8))
    for node in strategy.node_config:
        for sync in [node.synchronizer] + list(node.part_config):
            if isinstance(sync, PSSynchronizer):
                sync.hierarchical = 'never'
    static = static_collective_schedule(strategy, gi, 8, nodes=2)
    assert all(e['hier'] == 0 for e in static)


# -- AutoStrategy ---------------------------------------------------------

def test_autostrategy_rank_flip_on_memory_tight_budget():
    """On a tight per-device budget the replicated-update AllReduce
    candidates are pruned (full f32 slots) while the update-shard
    candidate fits — the freed opt-slot memory is exactly what makes
    it the pick."""
    from autodist_tpu.strategy import builders as b
    gi = _make_gi({'w%d' % i: (1024, 512) for i in range(4)})
    rs = _make_rs(8)
    # replicated peak = params + grads + 2 slots + staging = 48 MiB;
    # sharded peak = params + (grads + slots)/8 + staging = 27 MiB
    budget = 40 * MiB
    cands = [('AllReduce(chunk=128)', lambda: b.AllReduce()),
             ('AllReduce(update-shard)',
              lambda: b.AllReduce(weight_update_sharding='always'))]
    auto = AutoStrategy(memory_budget_bytes=budget, optimizer_slots=2,
                        candidates=cands)
    strategy = auto.build(gi, rs)
    assert strategy.cost['builder'] == 'AllReduce(update-shard)'
    assert [c.name for c in auto.last_infeasible] == \
        ['AllReduce(chunk=128)']
    # with a loose budget both fit — the flip was the budget's doing
    auto2 = AutoStrategy(memory_budget_bytes=None, optimizer_slots=2,
                         candidates=cands)
    auto2.build(gi, rs)
    assert len(auto2.last_ranked) == 2 and not auto2.last_infeasible
    # and the full default candidate set now carries the dimension
    from autodist_tpu.simulator.search import default_candidates
    assert any(name == 'AllReduce(update-shard)'
               for name, _ in default_candidates())
