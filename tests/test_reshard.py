"""Device-side resharding (ISSUE 9): layout planning (collective
choice by the redistribution cost model), the A->B->A bit-identity
property across every op kind, optimizer-slot co-movement, and the
executed elastic re-plan (AUTODIST_EXECUTE_REPLAN) migrating a live
loose-mode session with exact state."""
import shutil
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from autodist_tpu.const import AXIS_DATA
from autodist_tpu.parallel import reshard
from autodist_tpu.parallel.plan import ExecutionPlan
from autodist_tpu.strategy.base import (AllReduceSynchronizer,
                                        PSSynchronizer, Strategy,
                                        StrategyNode)
from autodist_tpu.strategy.adapter import (FunctionalModel,
                                           PytreeGraphItem)

SHAPES = {'w': (24, 16), 'u': (30, 8), 'b': (48,), 's': ()}


def make_gi():
    def init_fn(rng):
        return {k: jnp.zeros(s, jnp.float32) for k, s in SHAPES.items()}
    return PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))


def make_strategy(cfg):
    """cfg: {var: None (replicated AR) | (partitioner, num_shards)}."""
    s = Strategy()
    for name, c in cfg.items():
        if c is None:
            s.node_config.append(StrategyNode(
                var_name=name, synchronizer=AllReduceSynchronizer()))
        else:
            part, nsh = c
            s.node_config.append(StrategyNode(
                var_name=name, partitioner=part,
                part_config=[PSSynchronizer() for _ in range(nsh)]))
    return s


def make_plans(gi, cfg_a, cfg_b):
    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    return (ExecutionPlan(make_strategy(cfg_a), gi, mesh),
            ExecutionPlan(make_strategy(cfg_b), gi, mesh))


def place(plan, host):
    return {k: jax.device_put(plan.pad_host(k, jnp.asarray(v)),
                              plan.var_sharding(k))
            for k, v in host.items()}


A_CFG = {'w': ('8,1', 8),    # even shard, axis 0
         'u': ('2,1', 2),    # UNEVEN shard (30 rows over 8: pad to 32)
         'b': None, 's': None}
B_CFG = {'w': ('1,8', 8),    # shard axis flips 0 -> 1
         'u': None,          # sharded -> replicated
         'b': ('8', 8),      # replicated -> sharded
         's': None}          # scalar stays replicated


def test_plan_reshard_picks_expected_collectives():
    gi = make_gi()
    pa, pb = make_plans(gi, A_CFG, B_CFG)
    kinds = {o.var_name: o.kind for o in reshard.plan_reshard(pa, pb)}
    assert kinds == {'w': 'all_to_all',    # clean axis flip, no pads
                     'u': 'all_gather',    # sharded -> replicated
                     'b': 'shard',         # replicated -> sharded
                     's': 'noop'}
    # zero-wire ops report zero bytes; real moves report (n-1)/n
    ops = {o.var_name: o for o in reshard.plan_reshard(pa, pb)}
    assert ops['s'].wire_bytes == 0 and ops['b'].wire_bytes == 0
    assert ops['w'].wire_bytes > 0 and ops['w'].est_time_s > 0


def test_padded_axis_change_uses_gather_scatter():
    """all_to_all's tiled split cannot carry padding: an uneven source
    re-sharding onto another axis must take the single-program
    gather+re-slice instead."""
    gi = make_gi()
    pa, pb = make_plans(gi, {'u': ('2,1', 2)}, {'u': ('1,2', 2)})
    ops = {o.var_name: o.kind for o in reshard.plan_reshard(pa, pb)}
    assert ops['u'] == 'gather_scatter'


def test_roundtrip_bit_identical_all_kinds():
    """ISSUE 9 acceptance: A -> B -> A is bit-identical, across every
    op kind (all_to_all, all_gather, shard, gather_scatter, noop) —
    resharding is pure data movement."""
    gi = make_gi()
    pa, pb = make_plans(gi, A_CFG, B_CFG)
    rng = np.random.RandomState(0)
    host = {k: rng.randn(*s).astype('f4') if s
            else np.float32(rng.randn()) for k, s in SHAPES.items()}
    arrays = place(pa, host)
    b_arrays, _, ops_ab = reshard.apply_reshard(pa, pb, arrays)
    # values under B are exactly the host values (unpadded view)
    for k in SHAPES:
        np.testing.assert_array_equal(
            np.asarray(pb.unpad_host(k, b_arrays[k])), host[k])
    back, _, ops_ba = reshard.apply_reshard(pb, pa, b_arrays)
    for k in SHAPES:
        assert (np.asarray(back[k]) == np.asarray(arrays[k])).all(), k
    # exercised kinds cover the table
    kinds = {o.kind for o in ops_ab} | {o.kind for o in ops_ba}
    assert {'all_to_all', 'all_gather', 'shard', 'noop'} <= kinds


def test_roundtrip_through_padded_gather_scatter():
    gi = make_gi()
    pa, pb = make_plans(gi, {'u': ('2,1', 2)}, {'u': ('1,2', 2)})
    rng = np.random.RandomState(1)
    host = {'u': rng.randn(30, 8).astype('f4')}
    arrays = place(pa, host)
    b_arrays, _, _ = reshard.apply_reshard(pa, pb, arrays)
    np.testing.assert_array_equal(
        np.asarray(pb.unpad_host('u', b_arrays['u'])), host['u'])
    back, _, _ = reshard.apply_reshard(pb, pa, b_arrays)
    assert (np.asarray(back['u']) == np.asarray(arrays['u'])).all()


def test_optimizer_slots_ride_the_same_op():
    """`extra` arrays shaped like their variable (optimizer slots)
    move through the same compiled fn, staying aligned with the
    variable's layout."""
    gi = make_gi()
    pa, pb = make_plans(gi, {'w': ('8,1', 8)}, {'w': ('1,8', 8)})
    rng = np.random.RandomState(2)
    host = {'w': rng.randn(24, 16).astype('f4')}
    slot = rng.randn(24, 16).astype('f4')
    arrays = place(pa, host)
    extra = {'w': [jax.device_put(pa.pad_host('w', jnp.asarray(slot)),
                                  pa.var_sharding('w'))]}
    b_arrays, b_extra, _ = reshard.apply_reshard(pa, pb, arrays,
                                                 extra=extra)
    np.testing.assert_array_equal(
        np.asarray(pb.unpad_host('w', b_extra['w'][0])), slot)
    assert b_extra['w'][0].sharding == b_arrays['w'].sharding


def test_mismatched_meshes_refused():
    gi = make_gi()
    pa, _ = make_plans(gi, A_CFG, B_CFG)
    mesh1 = Mesh(np.asarray(jax.devices()[:4]), (AXIS_DATA,))
    pb = ExecutionPlan(make_strategy(B_CFG), gi, mesh1)
    with pytest.raises(ValueError, match='one mesh'):
        reshard.apply_reshard(pa, pb, {})


# -- executed re-plan: live migration through the reshard path ------------

HAVE_GXX = shutil.which('g++') is not None


@pytest.mark.skipif(not HAVE_GXX, reason='g++ unavailable')
def test_executed_replan_migrates_live_session(monkeypatch, service):
    """AUTODIST_EXECUTE_REPLAN: a live 2->3 worker re-plan runs the
    epoch-swap handshake (stage -> peer ack quorum -> armed boundary)
    and migrates the chief's session through the reshard path at the
    commit boundary — compiled steps drop, the plan swaps to the
    re-ranked PS-family strategy (re-keying now LEGAL under the
    handshake), and the variable state is bit-exact with a run that
    never migrated but trained the same number of steps (values are
    moved, never recomputed)."""
    import autodist_tpu as ad
    from autodist_tpu.runtime.coord_client import CoordClient
    from autodist_tpu.runtime.session import admit_worker
    from autodist_tpu.utils.loose_harness import (ack_staged_swaps,
                                                  single_process_loose_env)

    port = service
    monkeypatch.setenv('AUTODIST_PEER_FAILURE_POLICY', 'exclude')
    monkeypatch.setenv('AUTODIST_HEARTBEAT_TIMEOUT', '5.0')

    def run_once(execute_replan, steps=5, train_total=None, join_at=1,
                 dim=24):
        monkeypatch.setenv('AUTODIST_EXECUTE_REPLAN',
                           '1' if execute_replan else '0')
        with single_process_loose_env(port, depth=1):
            autodist = ad.AutoDist(
                resource_info={'nodes': [
                    {'address': 'localhost', 'gpus': [0],
                     'chief': True, 'network_bandwidth': 100}]},
                strategy_builder=ad.strategy.PS(staleness=1))
            rng = np.random.RandomState(0)
            W0 = rng.randn(dim, 3).astype(np.float32)
            feed = rng.randn(8, dim).astype(np.float32)
            with autodist.scope():
                x = ad.placeholder(shape=[None, dim],
                                   dtype=np.float32, name='x')
                W = ad.Variable(W0, name='W')
                loss = ad.ops.reduce_mean(
                    ad.ops.square(ad.ops.matmul(x, W)))
                train_op = ad.optimizers.SGD(0.1).minimize(loss, [W])
                autodist._build()
                ns = autodist._transformed[0].id

                def drive(c, me, ordinal, start_step):
                    """Publish steps; in the swap leg also speak the
                    ack half of the handshake, publishing PAST the
                    armed boundary so the chief's staleness gate never
                    blocks its walk to step B."""
                    seen, s = set(), start_step
                    deadline = time.time() + 20.0
                    while time.time() < deadline:
                        s += 1
                        c.heartbeat('%s/%s' % (ns, me))
                        c.publish_step(me, s, prefix='%s/step/' % ns)
                        if execute_replan:
                            _, b = ack_staged_swaps(c, ns, ordinal,
                                                    seen)
                            if b and s >= b + 5:
                                break
                        elif s >= steps:
                            break
                        time.sleep(0.03)
                    c.set('done/%s/%s' % (ns, me), '1')
                    c.publish_step(me, 1 << 30,
                                   prefix='%s/step/' % ns)
                    c.close()

                def peer():
                    c = CoordClient(('127.0.0.1', port))
                    gen = c.incr('fence/%s/p1' % ns, 0)
                    c.fence('fence/%s/p1' % ns, gen)
                    c.heartbeat('%s/p1' % ns)
                    c.barrier('%s/session/init' % ns, 2,
                              timeout_s=60.0)
                    drive(c, 'p1', 1, 0)

                def joiner():
                    c = CoordClient(('127.0.0.1', port))
                    deadline = time.time() + 60.0
                    while time.time() < deadline:
                        if c.incr('%s/step/p1' % ns, 0) >= join_at:
                            break
                        time.sleep(0.02)
                    admit = admit_worker(c, ns)
                    me = admit['worker']
                    drive(c, me, int(me[1:]), admit['adopted_step'])

                threads = [threading.Thread(target=peer, daemon=True),
                           threading.Thread(target=joiner, daemon=True)]
                for t in threads:
                    t.start()
                sess = autodist.create_distributed_session()
                trained = 0
                for _ in range(steps):
                    sess.run(train_op, {x: feed})
                    trained += 1
                if execute_replan:
                    # the re-rank thread stages the swap; the armed
                    # boundary B lands at the start of a later step —
                    # keep TRAINING (fetch-only runs never advance the
                    # step counter, so they can never reach B) until
                    # the migration lands or the bounded wait expires
                    deadline = time.time() + 30.0
                    while time.time() < deadline and trained < 60:
                        if any(r.get('migrated')
                               or r.get('migration_error')
                               or r.get('migration_skipped')
                               for r in sess.health_stats.get(
                                   'replans', [])):
                            break
                        sess.run(train_op, {x: feed})
                        trained += 1
                else:
                    # match the swap leg's step count exactly: the
                    # bit-exactness claim is per-step
                    for _ in range((train_total or steps) - trained):
                        sess.run(train_op, {x: feed})
                        trained += 1
                w = sess.get_variable_value('W')
                stats = dict(sess.health_stats)
                sess.close()
                for t in threads:
                    t.join(timeout=25.0)
        return np.asarray(w), stats, trained

    w_mig, stats_mig, n_mig = run_once(True)
    w_plain, stats_plain, n_plain = run_once(False, train_total=n_mig)

    plain_replans = stats_plain.get('replans', [])
    mig_replans = stats_mig.get('replans', [])
    assert plain_replans and not any(r.get('migrated')
                                     for r in plain_replans)
    migrated = [r for r in mig_replans if r.get('migrated')]
    assert migrated, mig_replans
    mig = migrated[0]['migration']
    assert mig['reshard']['vars'] >= 1
    assert mig['builder']
    # the handshake audit trail: the entry records the armed boundary
    swap = migrated[0].get('swap')
    assert swap and swap['gen'] >= 1 and swap['boundary'] >= 1
    # the migration moved values, never recomputed them: final state
    # is bit-exact with a never-migrated run of the same length
    assert n_plain == n_mig
    assert np.abs(w_plain - w_mig).max() == 0.0
