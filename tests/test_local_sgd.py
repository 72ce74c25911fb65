"""Local-SGD H-step window (ISSUE 16): strategy/ENV plumbing, the
cost model's H-fold wire amortization and weak-link ranking flip,
lazy-row bit-stability across a window, and the loose-mode session's
window machinery — round-scoped sync accounting, the H=1 equivalence
pin, window telescoping, and the partial-window-dropped contract.

The session tests run single-process against a live coord_service on
a private port (skipped without g++, like tests/test_async_ps.py).
"""
import shutil
from contextlib import contextmanager

import numpy as np
import pytest

import jax.numpy as jnp

from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.simulator import cost_model, search
from autodist_tpu.strategy import builders
from autodist_tpu.strategy.adapter import FunctionalModel, PytreeGraphItem

HAVE_GXX = shutil.which('g++') is not None


def make_gi(shapes):
    def init_fn(rng):
        return {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    return PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))


def make_rs(n=8, nodes=1):
    node_list = []
    for i in range(nodes):
        node = {'address': 'host%d' % i, 'cpus': [0],
                'network_bandwidth': 100,
                'tpus': list(range(n // nodes))}
        if i == 0:
            node['chief'] = True
        node_list.append(node)
    return ResourceSpec(resource_info={'nodes': node_list})


# -- strategy plumbing ----------------------------------------------------

def test_ps_local_steps_roundtrips_and_defaults():
    """Every PS-family builder threads ``local_steps`` into its
    PSSynchronizer(s), the value survives the to_dict/from_dict wire
    format, and a legacy serialized strategy (no key) defaults to 1."""
    from autodist_tpu.strategy.base import Strategy
    gi = make_gi({'w': (64, 8)})
    rs = make_rs(8)
    for builder in (builders.PS(local_steps=4),
                    builders.PSLoadBalancing(local_steps=4),
                    builders.PartitionedPS(local_steps=4)):
        strat = builder.build(gi, rs)
        rt = Strategy.from_dict(strat.to_dict())
        for node in rt.node_config:
            syncs = node.part_config if node.part_config \
                else [node.synchronizer]
            for s in syncs:
                if getattr(s, 'kind', '') == 'PS':
                    assert s.local_steps == 4, type(builder).__name__
    # legacy dict: drop the key, reload -> H=1 (today's per-step sync)
    d = builders.PS(local_steps=4).build(gi, rs).to_dict()
    for node in d['node_config']:
        node['synchronizer'].pop('local_steps')
    legacy = Strategy.from_dict(d)
    assert all(n.synchronizer.local_steps == 1
               for n in legacy.node_config)


def test_strategy_local_steps_helper():
    """``strategy_local_steps`` is the tightest PS window of the
    strategy (mixed windows -> min); strategies with no PS-synced
    variable report 1 (nothing to amortize)."""
    gi = make_gi({'w': (64, 8)})
    rs = make_rs(8)
    assert cost_model.strategy_local_steps(
        builders.PS(local_steps=8).build(gi, rs)) == 8
    assert cost_model.strategy_local_steps(
        builders.PS().build(gi, rs)) == 1
    assert cost_model.strategy_local_steps(
        builders.AllReduce().build(gi, rs)) == 1


# -- cost model: H-fold amortization + the ranking flip -------------------

def test_local_sgd_ranking_flips_on_weak_link():
    """The AutoStrategy contract of the window knob: on a pure-ICI
    single-node spec the per-step H=1 PS stays ahead of every
    PS(H>1) candidate (the divergence haircut has nothing to buy
    back), while on a multi-node spec the DCN wire term dominates
    and an H>1 window overtakes the H=1 control."""
    gi = make_gi({'w1': (512, 512), 'w2': (512, 512)})
    feas, _ = search.rank(gi, make_rs(8, nodes=1))
    byname = {c.name: c for c in feas}
    for h in (2, 4, 8, 16):
        assert byname['PS'].rank < byname['PS(H=%d)' % h].rank, h
    feas, _ = search.rank(gi, make_rs(8, nodes=2))
    byname = {c.name: c for c in feas}
    assert any(byname['PS(H=%d)' % h].rank < byname['PS'].rank
               for h in (2, 4, 8, 16)), \
        {n: c.rank for n, c in byname.items() if n.startswith('PS')}
    # the report and the strategy.cost summary both carry the window
    assert byname['PS(H=8)'].report.local_steps == 8
    assert byname['PS(H=8)'].strategy.cost['local_steps'] == 8
    assert byname['PS'].report.local_steps == 1


def test_local_sgd_amortizes_only_ps_wire():
    """predict() at H>1 divides PS wire terms by H (plus the window
    averaging pass and divergence haircut); an AllReduce strategy is
    untouched by the knob — its entries are not PS-synced."""
    gi = make_gi({'w': (256, 256)})
    rs = make_rs(8, nodes=2)
    ps1 = cost_model.predict(builders.PS().build(gi, rs), gi, rs)
    ps8 = cost_model.predict(builders.PS(local_steps=8).build(gi, rs),
                             gi, rs)
    assert ps8.predicted_step_time_s < ps1.predicted_step_time_s
    assert ps8.local_steps == 8
    ar = cost_model.predict(builders.AllReduce().build(gi, rs), gi, rs)
    assert ar.local_steps == 1


# -- ENV knobs ------------------------------------------------------------

def test_local_steps_env_parse_and_validation(monkeypatch):
    from autodist_tpu.const import ENV
    monkeypatch.delenv('AUTODIST_LOCAL_STEPS', raising=False)
    assert ENV.AUTODIST_LOCAL_STEPS.val == 0   # 0 = defer to strategy
    monkeypatch.setenv('AUTODIST_LOCAL_STEPS', '4')
    assert ENV.AUTODIST_LOCAL_STEPS.val == 4
    monkeypatch.setenv('AUTODIST_LOCAL_STEPS', '-1')
    with pytest.raises(ValueError):
        ENV.AUTODIST_LOCAL_STEPS.val
    monkeypatch.delenv('AUTODIST_LOCAL_SGD_AVERAGE', raising=False)
    assert ENV.AUTODIST_LOCAL_SGD_AVERAGE.val is True   # default on
    monkeypatch.setenv('AUTODIST_LOCAL_SGD_AVERAGE', '0')
    assert ENV.AUTODIST_LOCAL_SGD_AVERAGE.val is False


def test_local_steps_forwarded_to_workers():
    """Every loose worker must agree on the window length (round-
    scoped gates deadlock otherwise — the data-plane model's
    LOCAL_SGD_STEP_GATE counterexample) and on the merge rule, so
    both knobs ride the coordinator's forwarded-flags list."""
    from autodist_tpu.runtime.coordinator import _FORWARDED_FLAGS
    names = {f.name for f in _FORWARDED_FLAGS}
    assert 'AUTODIST_LOCAL_STEPS' in names
    assert 'AUTODIST_LOCAL_SGD_AVERAGE' in names


# -- lazy-row optimizers across a window ----------------------------------

@pytest.mark.parametrize('opt_name', ['LazyAdam', 'LazyMomentum'])
def test_lazy_rows_bit_stable_across_window(opt_name):
    """Local-SGD composes with the row-sparse plane because untouched
    embedding rows stay BIT-identical through all H local steps —
    weights and same-shaped slot state — so the window delta is zero
    exactly on untouched rows and the round push ships the window-
    averaged touched-row union, not the table."""
    from autodist_tpu.frontend import optimizers
    opt = getattr(optimizers, opt_name)(0.01)
    rng = np.random.RandomState(0)
    value = jnp.asarray(rng.randn(16, 4).astype(np.float32))
    state = opt.tx.init(value)
    touched = (3, 7, 11)
    grad = np.zeros((16, 4), np.float32)
    for r in touched:
        grad[r] = rng.randn(4).astype(np.float32)
    v, st = value, state
    for _ in range(4):   # one H=4 window
        v, st = opt._lazy_row_update(jnp.asarray(grad), st, v)
    v = np.asarray(v)
    base = np.asarray(value)
    untouched = [r for r in range(16) if r not in touched]
    np.testing.assert_array_equal(v[untouched], base[untouched])
    assert not np.array_equal(v[list(touched)], base[list(touched)])
    # same-shaped slots (moments / velocity) row-freeze identically
    import jax
    for new, old in zip(jax.tree_util.tree_leaves(st),
                        jax.tree_util.tree_leaves(state)):
        if getattr(new, 'shape', None) == value.shape:
            np.testing.assert_array_equal(
                np.asarray(new)[untouched],
                np.asarray(old)[untouched])


# -- loose-mode session window machinery ----------------------------------

@contextmanager
def _loose_session(coord_port, h, depth=1, dim=48, seed=0):
    """Single-process loose-mode session at window length ``h`` (the
    build-sees-2/session-sees-1 dance shared with test_async_ps.py).
    Yields (sess, train_op, x placeholder, W0, feed)."""
    import autodist_tpu as ad
    from autodist_tpu.utils.loose_harness import single_process_loose_env
    with single_process_loose_env(coord_port, depth) as \
            session_sees_one:
        autodist = ad.AutoDist(
            resource_info={'nodes': [
                {'address': 'localhost', 'gpus': [0], 'chief': True,
                 'network_bandwidth': 100}]},
            strategy_builder=ad.strategy.PS(staleness=2, local_steps=h))
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
            try:
                yield sess, train_op, x, W0, feed
            finally:
                sess.close()


def _serial_ground_truth(W0, feed, steps, lr=0.1):
    """One worker's serial trajectory in numpy: grad of mean((xW)^2)
    wrt W is 2/(n*m) * x^T (x W)."""
    W = W0.astype(np.float32).copy()
    denom = np.float32(feed.shape[0] * W0.shape[1])
    for _ in range(steps):
        g = (np.float32(2.0) / denom) * (feed.T @ (feed @ W))
        W = W - np.float32(lr) * g
    return W


@pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')
def test_h1_sync_rounds_equal_train_steps(coord_port):
    """The H=1 equivalence pin (satellite 3): with no window every
    train step IS a sync round, so ps_stats' per-round pull/push
    divides are bit-for-bit the legacy per-step ones, and the math
    tracks the serial trajectory unchanged."""
    with _loose_session(coord_port, h=1) as (sess, train_op, x, W0, feed):
        for _ in range(5):
            sess.run(train_op, {x: feed})
        got = sess.get_variable_value('W')
        stats = sess.ps_stats
    pipe = stats['pipeline']
    assert pipe['local_steps'] == 1
    assert pipe['train_steps'] == 5
    assert pipe['sync_rounds'] == pipe['train_steps']
    np.testing.assert_allclose(got, _serial_ground_truth(W0, feed, 5),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')
def test_window_round_accounting(coord_port):
    """At H=4 the wire phases happen once per SYNC ROUND: 8 train
    steps = 2 rounds of pull/push, and the pipeline stats divide by
    rounds (dividing by train steps would understate per-round
    averages 4x — the satellite-3 fix)."""
    with _loose_session(coord_port, h=4) as (sess, train_op, x, W0, feed):
        for _ in range(8):
            sess.run(train_op, {x: feed})
        stats = sess.ps_stats
    pipe = stats['pipeline']
    assert pipe['local_steps'] == 4
    assert pipe['train_steps'] == 8
    assert pipe['sync_rounds'] == 2
    assert pipe['pull_s'] > 0 and pipe['push_s'] > 0


@pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')
def test_window_delta_telescopes_to_serial(coord_port):
    """One worker's window delta (state-after-H-local-steps minus the
    round's pulled base) telescopes to the sequential trajectory: the
    H=4 final state matches H=1 (and the analytic serial path) up to
    float reassociation noise."""
    finals = {}
    for h in (1, 4):
        with _loose_session(coord_port, h=h, seed=7) as (
                sess, train_op, x, W0, feed):
            for _ in range(8):
                sess.run(train_op, {x: feed})
            finals[h] = sess.get_variable_value('W')
    np.testing.assert_allclose(finals[4], finals[1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(finals[4],
                               _serial_ground_truth(W0, feed, 8),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')
def test_partial_window_is_dropped_at_close(coord_port):
    """The round is the atomic unit: 6 train steps at H=4 complete
    one sync round, and the 2-step tail never reaches the PS — the
    authoritative read serves the round-1 state (4 serial steps)."""
    with _loose_session(coord_port, h=4) as (sess, train_op, x, W0, feed):
        for _ in range(6):
            sess.run(train_op, {x: feed})
        got = sess.get_variable_value('W')
        stats = sess.ps_stats
    assert stats['pipeline']['sync_rounds'] == 1
    np.testing.assert_allclose(got, _serial_ground_truth(W0, feed, 4),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')
def test_env_window_overrides_strategy(coord_port, monkeypatch):
    """AUTODIST_LOCAL_STEPS > 0 overrides the strategy's window (the
    operator's weak-link dial, forwarded to every worker so the
    round-scoped gates agree)."""
    monkeypatch.setenv('AUTODIST_LOCAL_STEPS', '2')
    with _loose_session(coord_port, h=1) as (sess, train_op, x, W0, feed):
        assert sess._local_steps == 2
        for _ in range(4):
            sess.run(train_op, {x: feed})
        stats = sess.ps_stats
    assert stats['pipeline']['sync_rounds'] == 2
    assert stats['pipeline']['local_steps'] == 2
