"""Bucketed gradient synchronization (parallel/plan.py).

Pins the tentpole contract (ISSUE 1): ``sync_gradients`` emits ONE
collective per byte-capped bucket — no single whole-group concat when a
group exceeds the cap — with bucketed results elementwise-EQUAL to
per-variable reduction, across dtypes and compressors; plus cap
boundary cases (grad larger than cap, cap=1), reverse-production
emission order, deterministic bucket assignment, and the capped ZeRO
reduce-scatter path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import AXIS_DATA, BUCKET_BYTES_PER_CHUNK
from autodist_tpu.frontend import graph as fe
from autodist_tpu.parallel.plan import (ExecutionPlan, ShardedGrad,
                                        bucket_bytes_cap, pack_buckets)
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.parallel.axes import shard_map as _shard_map
from autodist_tpu.strategy import AllReduce, PartitionedPS
from autodist_tpu.strategy.adapter import (FunctionalModel,
                                           PytreeGraphItem,
                                           grad_bucket_layout)

N_DEV = 8


def _make_plan(shapes, builder, dtype=jnp.float32):
    """(plan, sources, mesh) over the 8-device CPU mesh for a pytree of
    ``shapes`` synced per ``builder``'s strategy."""
    def init_fn(rng):
        return {'v%02d' % i: jnp.zeros(s, dtype)
                for i, s in enumerate(shapes)}

    gi = PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))
    rs = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(N_DEV)), 'network_bandwidth': 100}]})
    strategy = builder.build(gi, rs)
    mesh = Mesh(np.asarray(jax.devices()[:N_DEV]), (AXIS_DATA,))
    plan = ExecutionPlan(strategy, gi, mesh)
    sources = list(gi.trainable_var_op_to_var.values())
    return plan, sources, mesh


def _run_sync(plan, sources, mesh, stacked):
    """Run sync_gradients inside shard_map on per-replica gradient
    stacks (leading dim = replicas); returns the synced values with the
    per-replica stack restored (every row holds the reduced value)."""
    def sync(*gs):
        gs = [g[0] for g in gs]   # strip this replica's leading dim
        out = plan.sync_gradients(sources, list(gs), fe.Env({}, {}))
        return tuple((o.value if isinstance(o, ShardedGrad) else o)[None]
                     for o in out)

    f = jax.jit(_shard_map(
        sync, mesh, tuple(P(AXIS_DATA) for _ in stacked),
        tuple(P(AXIS_DATA) for _ in stacked)))
    return [np.asarray(o) for o in f(*stacked)]


def _stacked_grads(shapes, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(N_DEV, *s).astype('f4')).astype(dtype)
            for s in shapes]


# -- pure packer -------------------------------------------------------------

def test_pack_buckets_cap_and_boundaries():
    items = [('a', 400), ('b', 400), ('c', 400), ('d', 4000), ('e', 100)]
    # byte cap: a+b fit, c closes at the cap, the oversized d gets its
    # own bucket (never merged), e follows
    assert pack_buckets(items, 800) == [['a', 'b'], ['c'], ['d'], ['e']]
    # cap=1: every item its own bucket
    assert pack_buckets(items, 1) == [[k] for k, _ in items]
    # max_vars binds even under a huge cap
    assert pack_buckets(items, 1 << 40, max_vars=2) == \
        [['a', 'b'], ['c', 'd'], ['e']]
    assert pack_buckets([], 100) == []


def test_pack_buckets_deterministic():
    rng = np.random.RandomState(7)
    items = [('v%03d' % i, int(rng.randint(1, 1 << 20)))
             for i in range(200)]
    first = pack_buckets(list(items), 1 << 20, max_vars=16)
    for _ in range(3):   # same inputs -> same buckets, every process
        assert pack_buckets(list(items), 1 << 20, max_vars=16) == first


def test_bucket_bytes_cap_derivation(monkeypatch):
    monkeypatch.delenv('AUTODIST_BUCKET_BYTES', raising=False)
    assert bucket_bytes_cap(4) == 4 * BUCKET_BYTES_PER_CHUNK
    assert bucket_bytes_cap(0) == 128 * BUCKET_BYTES_PER_CHUNK
    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '12345')
    assert bucket_bytes_cap(4) == 12345


# -- collective count: one psum per bucket (reduce-fn spy) -------------------

def _spy_reduce(monkeypatch):
    """Wrap ExecutionPlan._reduce_fn so every reduce invocation (one per
    emitted collective) records the flattened element count."""
    calls = []
    orig = ExecutionPlan._reduce_fn

    def spy(self, spec):
        fn = orig(self, spec)

        def wrapped(g):
            calls.append(int(g.size))
            return fn(g)
        return wrapped

    monkeypatch.setattr(ExecutionPlan, '_reduce_fn', spy)
    return calls


def test_one_collective_per_bucket_not_one_mega_bucket(monkeypatch):
    # 6 x 400 B gradients, cap 1000 B -> 3 buckets of 2, NOT one
    # whole-group concat (the pre-bucketing behavior)
    shapes = [(100,)] * 6
    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '1000')
    calls = _spy_reduce(monkeypatch)
    plan, sources, mesh = _make_plan(shapes, AllReduce(chunk_size=128))
    stacked = _stacked_grads(shapes, jnp.float32)
    _run_sync(plan, sources, mesh, stacked)
    assert calls == [200, 200, 200], calls
    stats = plan.last_bucket_stats
    assert [b['vars'] for b in stats] == [2, 2, 2]
    assert all(b['bytes'] == 800 for b in stats)
    # reverse gradient-production order: the backward produces v05's
    # gradient first, so the first emitted bucket must cover the tail
    assert stats[0]['members'][0] == 'v05'
    assert stats[-1]['members'][-1] == 'v00'


def test_grad_larger_than_cap_gets_own_bucket(monkeypatch):
    shapes = [(100,), (1000,), (50,)]   # 400 B, 4 KB, 200 B
    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '800')
    calls = _spy_reduce(monkeypatch)
    plan, sources, mesh = _make_plan(shapes, AllReduce(chunk_size=128))
    stacked = _stacked_grads(shapes, jnp.float32)
    _run_sync(plan, sources, mesh, stacked)
    # reverse order: v02 alone, oversized v01 alone, v00 alone
    assert calls == [50, 1000, 100], calls
    assert [b['members'] for b in plan.last_bucket_stats] == \
        [['v02'], ['v01'], ['v00']]


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize('compressor',
                         ['NoneCompressor', 'HorovodCompressor'])
def test_bucketed_equals_per_variable_reduction(monkeypatch, dtype,
                                                compressor):
    """Acceptance: bucketed output elementwise-EQUAL to per-variable
    reduction (cap=1 packs every gradient alone — the per-variable
    program) across dtypes and compressors."""
    shapes = [(40,), (8, 16), (3, 5, 7), (64,), (11,)]
    stacked = _stacked_grads(shapes, dtype)

    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '600')
    plan, sources, mesh = _make_plan(
        shapes, AllReduce(chunk_size=128, compressor=compressor), dtype)
    bucketed = _run_sync(plan, sources, mesh, stacked)
    assert any(b['vars'] > 1 for b in plan.last_bucket_stats)

    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '1')
    plan1, sources1, mesh1 = _make_plan(
        shapes, AllReduce(chunk_size=128, compressor=compressor), dtype)
    pervar = _run_sync(plan1, sources1, mesh1, stacked)
    assert all(b['vars'] == 1 for b in plan1.last_bucket_stats)

    for b, p in zip(bucketed, pervar):
        assert b.dtype == p.dtype
        np.testing.assert_array_equal(b, p)


def test_bucketed_mean_is_correct(monkeypatch):
    """Against an independent reference: pmean over replicas == numpy
    mean of the per-replica stacks (f32, exact: psum adds in the same
    pairwise order for every element)."""
    shapes = [(32,), (16, 4)]
    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '64')
    plan, sources, mesh = _make_plan(shapes, AllReduce(chunk_size=128))
    stacked = _stacked_grads(shapes, jnp.float32)
    outs = _run_sync(plan, sources, mesh, stacked)
    for out, g in zip(outs, stacked):
        want = np.asarray(g).mean(axis=0)
        np.testing.assert_allclose(out[0], want, rtol=1e-6, atol=1e-6)
        # every replica carries the same reduced value
        for r in range(1, N_DEV):
            np.testing.assert_array_equal(out[r], out[0])


def test_bucket_assignment_deterministic_across_plans(monkeypatch):
    """Two independently built plans (fresh strategy/plan objects, same
    inputs) must emit identical bucket layouts — divergent layouts
    across SPMD processes would deadlock the collective."""
    shapes = [(100,), (30,), (256,), (7,), (100,)]
    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '700')
    stacked = _stacked_grads(shapes, jnp.float32)
    layouts = []
    for _ in range(2):
        plan, sources, mesh = _make_plan(shapes,
                                         AllReduce(chunk_size=128))
        _run_sync(plan, sources, mesh, stacked)
        layouts.append([(b['members'], b['bytes'])
                       for b in plan.last_bucket_stats])
    assert layouts[0] == layouts[1]
    # and the static layout (adapter surface) agrees with the emission
    def init_fn(rng):
        return {'v%02d' % i: jnp.zeros(s, jnp.float32)
                for i, s in enumerate(shapes)}
    gi = PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))
    rs = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(N_DEV)), 'network_bandwidth': 100}]})
    static = grad_bucket_layout(AllReduce(chunk_size=128).build(gi, rs),
                                gi)
    assert [(b['vars'], b['bytes']) for b in static] == \
        [(m, by) for m, by in layouts[0]]


def test_chunk_size_threads_through_strategy_serialization():
    """builders -> proto -> (de)serialize -> VarPlan keeps chunk_size."""
    shapes = [(10,)] * 3
    plan, sources, _ = _make_plan(shapes, AllReduce(chunk_size=2))
    assert all(p.chunk_size == 2 for p in plan.var_plans.values())
    from autodist_tpu.strategy.base import Strategy
    def init_fn(rng):
        return {'v%02d' % i: jnp.zeros(s, jnp.float32)
                for i, s in enumerate(shapes)}
    gi = PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))
    rs = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'cpus': [0],
        'gpus': list(range(N_DEV)), 'network_bandwidth': 100}]})
    s = AllReduce(chunk_size=2).build(gi, rs)
    back = Strategy.from_dict(s.to_dict())
    assert all(n.synchronizer.chunk_size == 2 for n in back.node_config)


def test_capped_zero_reduce_scatter_exact(monkeypatch):
    """ZeRO path under the cap: chunked psum_scatter along a non-scatter
    axis is elementwise-identical to the whole-tensor collective."""
    shapes = [(16, 16)]
    stacked = _stacked_grads(shapes, jnp.float32)

    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', '256')
    plan, sources, mesh = _make_plan(shapes, PartitionedPS())
    assert any(p.state_sharded for p in plan.var_plans.values())
    capped = _run_sync(plan, sources, mesh, stacked)
    scat = [b for b in plan.last_bucket_stats
            if b['kind'] == 'psum_scatter']
    assert len(scat) == 4          # 1024 B / 256 B cap
    assert sum(b['bytes'] for b in scat) == 1024

    monkeypatch.setenv('AUTODIST_BUCKET_BYTES', str(1 << 30))
    plan2, sources2, mesh2 = _make_plan(shapes, PartitionedPS())
    whole = _run_sync(plan2, sources2, mesh2, stacked)
    assert len([b for b in plan2.last_bucket_stats
                if b['kind'] == 'psum_scatter']) == 1
    np.testing.assert_array_equal(capped[0], whole[0])
