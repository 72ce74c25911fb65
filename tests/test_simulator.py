"""Strategy simulator tests: golden α-β costs for known shapes/meshes,
the memory-budget property of AutoStrategy, rank consistency (bigger
tensors / slower links never predicted cheaper), static-vs-traced
schedule agreement, calibration fitting, and the tools/simulate.py
smoke (ISSUE 2 satellite: tier-1, CPU-fallback)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.simulator import calibrate, cost_model, search
from autodist_tpu.simulator.cost_model import (CostModelParams,
                                               collective_time, predict,
                                               wire_bytes)
from autodist_tpu.strategy import (AllReduce, AutoStrategy,
                                   PartitionedPS, Strategy)
from autodist_tpu.strategy.adapter import FunctionalModel, PytreeGraphItem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MiB = 1 << 20


def make_gi(shapes, axes=None, dtype=jnp.float32):
    """GraphItem over a dict of {name: shape}."""
    def init_fn(rng):
        return {k: jnp.zeros(s, dtype) for k, s in shapes.items()}
    return PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0,
                                           axes=axes))


def make_rs(n=8, device='tpus', topology=None, nodes=1):
    node_list = []
    for i in range(nodes):
        node = {'address': 'host%d' % i, 'cpus': [0],
                'network_bandwidth': 100,
                device: list(range(n // nodes))}
        if i == 0:
            node['chief'] = True
        node_list.append(node)
    info = {'nodes': node_list}
    if topology:
        info['topology'] = topology
    return ResourceSpec(resource_info=info)


# -- golden costs (pinned numbers for known shapes/meshes) ----------------

def test_collective_time_golden_ring_allreduce():
    # 4 MiB ring all-reduce over 8 devices at alpha=1us, beta=1e-11 s/B
    # (100 GB/s): 2*7*1e-6 + 2*(7/8)*4194304*1e-11
    t = collective_time('all_reduce', 4 * MiB, 8, 1e-6, 1e-11)
    assert t == pytest.approx(8.740032e-05, rel=1e-9)


def test_collective_time_golden_reduce_scatter_half():
    # the ZeRO half: 7*1e-6 + (7/8)*4194304*1e-11
    t = collective_time('psum_scatter', 4 * MiB, 8, 1e-6, 1e-11)
    assert t == pytest.approx(4.3700160e-05, rel=1e-9)
    # all-gather prices identically (same wire volume)
    assert collective_time('all_gather', 4 * MiB, 8, 1e-6, 1e-11) == t
    # RS + AG together == the ring all-reduce
    assert 2 * t == pytest.approx(
        collective_time('all_reduce', 4 * MiB, 8, 1e-6, 1e-11))


def test_collective_time_single_device_is_free():
    assert collective_time('all_reduce', 4 * MiB, 1, 1e-6, 1e-11) == 0.0


def test_predict_golden_single_var_allreduce():
    gi = make_gi({'w': (1024, 1024)})
    rs = make_rs(8)   # default TPU topology: 100 GB/s, 1 us
    s = AllReduce().build(gi, rs)
    rep = predict(s, gi, rs, num_replicas=8, optimizer_slots=2)
    # one bucket, no overlap discount on the last (only) bucket
    assert rep.num_collectives == 1
    assert rep.predicted_step_time_s == pytest.approx(8.740032e-05,
                                                      rel=1e-9)
    # params 4 MiB + grads 4 MiB + 2 f32 slots 8 MiB, no staging
    # (single-var bucket)
    assert rep.predicted_peak_bytes == 16 * MiB
    assert rep.memory['bucket_staging_bytes'] == 0
    # every priced entry's IR program passed the shape algebra, and
    # the certificate rides Strategy.cost via summary()
    assert rep.schedule_verified is True
    assert rep.summary()['schedule_verified'] is True


def test_wire_bytes_compressors():
    assert wire_bytes(4096, 'float32', 'NoneCompressor') == 4096
    assert wire_bytes(4096, 'float32', 'HorovodCompressor') == 2048
    # int8 blocks carry one f32 scale per AUTODIST_QUANT_BLOCK (256)
    # elements: 1024 int8 + 4 scales — the 4x headline never overstates
    assert wire_bytes(4096, 'float32', 'Int8RingCompressor') == \
        1024 + 4 * 4
    # bf16 params: the bf16 wire cast is a no-op, not a saving
    assert wire_bytes(2048, 'bfloat16', 'HorovodCompressor') == 2048


def test_zero_sharding_prices_scatter_plus_gather():
    gi = make_gi({'w': (1024, 64)})
    rs = make_rs(8)
    s = PartitionedPS().build(gi, rs)
    rep = predict(s, gi, rs, num_replicas=8)
    kinds = [b['kind'] for b in rep.breakdown]
    assert 'psum_scatter' in kinds and 'all_gather' in kinds
    # sharded state: grads + optimizer slots count 1/n
    full = 1024 * 64 * 4
    assert rep.memory['grads_bytes'] == full // 8
    assert rep.memory['params_bytes'] == full


# -- rank consistency: bigger tensors on slower links never cheaper -------

@pytest.mark.parametrize('kind', ['all_reduce', 'psum_scatter',
                                  'all_gather'])
def test_monotone_in_bytes(kind):
    sizes = [1 << k for k in range(8, 28, 4)]
    times = [collective_time(kind, b, 8, 1e-6, 1e-11) for b in sizes]
    assert times == sorted(times)
    assert all(t2 > t1 for t1, t2 in zip(times, times[1:]))


def test_monotone_in_link_speed():
    # higher beta (slower link) or higher alpha never predicts cheaper
    base = collective_time('all_reduce', 4 * MiB, 8, 1e-6, 1e-11)
    assert collective_time('all_reduce', 4 * MiB, 8, 1e-6, 1e-9) > base
    assert collective_time('all_reduce', 4 * MiB, 8, 1e-4, 1e-11) > base


def test_rank_consistency_end_to_end():
    """A model with 4x the bytes on a 10x slower link must never be
    predicted cheaper than the small model on the fast link, for every
    candidate builder."""
    gi_small = make_gi({'w': (512, 512), 'b': (512,)})
    gi_big = make_gi({'w': (1024, 1024), 'b': (1024,)})
    rs_fast = make_rs(8, topology={'ici_bandwidth_gbps': 100})
    rs_slow = make_rs(8, topology={'ici_bandwidth_gbps': 10})
    fast, _ = search.rank(gi_small, rs_fast)
    slow, _ = search.rank(gi_big, rs_slow)
    fast_by_name = {c.name: c for c in fast}
    for c in slow:
        other = fast_by_name[c.name]
        assert c.report.predicted_step_time_s >= \
            other.report.predicted_step_time_s, c.name


def test_multi_node_prices_dcn_link():
    gi = make_gi({'w': (1024, 1024)})
    one = predict(AllReduce().build(gi, make_rs(8)), gi, make_rs(8),
                  num_replicas=8)
    rs2 = make_rs(8, nodes=2)
    two = predict(AllReduce().build(gi, rs2), gi, rs2, num_replicas=8)
    assert two.cross_node and not one.cross_node
    assert two.predicted_step_time_s > one.predicted_step_time_s


# -- AutoStrategy: budget property + metadata -----------------------------

def test_auto_strategy_picks_and_annotates():
    gi = make_gi({'w': (256, 256), 'b': (256,)})
    rs = make_rs(8)
    builder = AutoStrategy()
    s = builder.build(gi, rs)
    assert s.cost is not None
    assert s.cost['rank'] == 0
    assert s.cost['predicted_step_time_s'] > 0
    assert builder.last_ranked and \
        builder.last_ranked[0].strategy is s
    # ranked order is by predicted step time
    times = [c.report.predicted_step_time_s
             for c in builder.last_ranked]
    assert times == sorted(times)


def test_auto_strategy_never_exceeds_memory_budget():
    gi = make_gi({'emb': (4096, 64), 'w1': (64, 256), 'w2': (256, 64)})
    rs = make_rs(8)
    # sweep budgets from generous down to the pruning region
    all_ranked, _ = search.rank(gi, rs)
    peaks = sorted(c.report.predicted_peak_bytes for c in all_ranked)
    for budget in [peaks[-1], (peaks[0] + peaks[-1]) // 2, peaks[0]]:
        builder = AutoStrategy(memory_budget_bytes=budget)
        s = builder.build(gi, rs)
        assert s.cost['predicted_peak_bytes'] <= budget
        for cand in builder.last_ranked:
            assert cand.report.predicted_peak_bytes <= budget


def test_auto_strategy_raises_when_nothing_fits():
    gi = make_gi({'w': (1024, 1024)})
    rs = make_rs(8)
    with pytest.raises(ValueError, match='memory'):
        AutoStrategy(memory_budget_bytes=1024).build(gi, rs)


def test_cost_metadata_serialization_roundtrip():
    gi = make_gi({'w': (256, 256)})
    rs = make_rs(8)
    s = AutoStrategy().build(gi, rs)
    s2 = Strategy.from_dict(s.to_dict())
    assert s2.cost == s.cost
    # hand-built strategies carry no cost block
    plain = AllReduce().build(gi, rs)
    assert plain.cost is None and 'cost' not in plain.to_dict()


def test_auto_strategy_on_captured_graph():
    """The tenth builder speaks the same GraphItem protocol as the
    other nine: a session-path captured graph (scalar + sparse vars)
    builds and annotates."""
    import autodist_tpu as ad
    from autodist_tpu.frontend import graph as fe
    from autodist_tpu.graph_item import GraphItem

    gi = GraphItem(graph=fe.Graph())
    with gi.graph:
        w = ad.Variable(np.zeros((12, 4), np.float32), name='w')
        emb = ad.Variable(np.zeros((10, 4), np.float32), name='emb')
        s = ad.Variable(0.5, name='s')
        x = ad.placeholder(shape=[None], dtype=np.int32, name='x')
        looked = ad.ops.embedding_lookup(emb, x)
        loss = ad.ops.reduce_mean(
            ad.ops.square(looked @ w.read().T)) + s
        ad.optimizers.SGD(0.1).minimize(loss, [w, emb, s])
    gi.prepare()
    strategy = AutoStrategy().build(gi, make_rs(4, device='gpus'))
    assert strategy.cost['predicted_step_time_s'] > 0
    assert len(strategy.node_config) == 3


# -- static schedule mirrors the traced plan ------------------------------

def test_static_schedule_matches_traced_bucket_layout():
    """static_collective_schedule must emit the SAME AR buckets (bytes,
    members, order) the execution plan records at trace time."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.const import AXIS_DATA
    from autodist_tpu.frontend import graph as fe
    from autodist_tpu.parallel.axes import shard_map
    from autodist_tpu.parallel.plan import (ExecutionPlan, ShardedGrad,
                                            static_collective_schedule)

    shapes = {'v%02d' % i: (128, 128) for i in range(6)}
    gi = make_gi(shapes)
    rs = make_rs(8, device='gpus')
    strategy = AllReduce(chunk_size=2).build(gi, rs)

    static = [e for e in static_collective_schedule(strategy, gi, 8)
              if e['phase'] == 'grad']

    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    plan = ExecutionPlan(strategy, gi, mesh)
    sources = list(gi.trainable_var_op_to_var.values())
    grads = [jnp.ones(s, jnp.float32) for s in shapes.values()]

    def sync(*gs):
        out = plan.sync_gradients(sources, list(gs), fe.Env({}, {}))
        return tuple(o.value if isinstance(o, ShardedGrad) else o
                     for o in out)

    f = shard_map(sync, mesh, tuple(P() for _ in grads),
                         tuple(P() for _ in grads))
    jax.eval_shape(f, *grads)   # trace only — records bucket stats
    traced = plan.last_bucket_stats
    assert [(e['bytes'], e['members']) for e in static] == \
        [(e['bytes'], e['members']) for e in traced]


# -- calibration ----------------------------------------------------------

def _timeline_row(nbytes, seconds, count=3):
    name = ('%%all-reduce.1 = f32[%d]{0} all-reduce(f32[%d]{0} %%p), '
            'replica_groups={}' % (nbytes // 4, nbytes // 4))
    return (name, seconds * count * 1e9, count)


def test_calibration_recovers_alpha_beta():
    alpha, beta = 5e-6, 4e-11
    n = 8
    rows = []
    for nbytes in (1 << 16, 1 << 20, 1 << 24):
        t = collective_time('all_reduce', nbytes, n, alpha, beta)
        rows.append(_timeline_row(nbytes, t))
    params = calibrate.calibrate_from_timeline(
        CostModelParams(), rows, num_replicas=n)
    assert params.calibrated
    assert params.alpha_ici_s == pytest.approx(alpha, rel=1e-3)
    assert params.beta_ici_s_per_byte == pytest.approx(beta, rel=1e-3)


def test_calibration_is_kind_aware():
    """A ZeRO run's timeline (reduce-scatter + all-gather rows only)
    must recover the SAME constants as an all-reduce timeline — each
    kind fits through its own cost shape — and async -start halves are
    dropped (operand-echoing shapes would double-count bytes)."""
    alpha, beta = 5e-6, 4e-11
    n = 8
    rows = []
    for nbytes in (1 << 16, 1 << 20, 1 << 24):
        t = collective_time('psum_scatter', nbytes, n, alpha, beta)
        rows.append(('%%reduce-scatter.3 = f32[%d]{0} reduce-scatter('
                     'f32[%d]{0} %%p)' % (nbytes // 4, nbytes // 4),
                     t * 3e9, 3))
        t = collective_time('all_gather', nbytes, n, alpha, beta)
        rows.append(('%%all-gather.9 = f32[%d]{0} all-gather('
                     'f32[%d]{0} %%p)' % (nbytes // 4, nbytes // 4),
                     t * 3e9, 3))
    # an async -start half with a tuple result echoing the operand:
    # must be ignored, not double-counted
    rows.append(('%all-reduce-start.1 = (f32[999]{0}, f32[999]{0}) '
                 'all-reduce-start(f32[999]{0} %p)', 5.0, 3))
    params = calibrate.calibrate_from_timeline(
        CostModelParams(), rows, num_replicas=n)
    assert params.calibrated
    assert params.alpha_ici_s == pytest.approx(alpha, rel=1e-3)
    assert params.beta_ici_s_per_byte == pytest.approx(beta, rel=1e-3)


def test_calibration_degrades_on_empty_timeline():
    base = CostModelParams()
    out = calibrate.calibrate_from_timeline(base, [], num_replicas=8)
    assert out is base and not out.calibrated
    # degenerate fit (one byte size) also degrades
    rows = [_timeline_row(4096, 1e-5)]
    out = calibrate.calibrate_from_timeline(base, rows, num_replicas=8)
    assert out is base


def test_calibration_from_missing_trace_dir(tmp_path):
    base = CostModelParams()
    out = calibrate.calibrate_from_trace(base, str(tmp_path), 8)
    assert out is base


# -- tools/simulate.py smoke (tier-1, CPU fallback) -----------------------

def test_simulate_cli_smoke():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'simulate.py'),
         '--model', 'tinylm', '--json'],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    cands = [c for c in rec['candidates'] if c.get('feasible')]
    assert len(cands) >= 9
    times = [c['predicted_step_time_s'] for c in cands]
    assert times == sorted(times)
    assert all(c['predicted_peak_bytes'] > 0 for c in cands)


def test_simulate_cli_table_and_budget():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'simulate.py'),
         '--model', 'tinylm', '--budget-gb', '0.000001'],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert 'pruned' in out.stdout


# -- serving-tier wire model (ISSUE 17) -----------------------------------

def test_serve_wire_cost_scales_and_casts():
    """The fleet's DCN draw scales linearly in replicas x poll rate,
    row traffic prices only the MISSES, and the int8 wire shrinks the
    bulk pull ~4x (blockscale header included)."""
    from autodist_tpu.simulator.cost_model import serve_wire_cost
    dense = 100 << 20
    one = serve_wire_cost(dense, replicas=1, poll_hz=2.0)
    four = serve_wire_cost(dense, replicas=4, poll_hz=2.0)
    assert four['snapshot_bytes_per_s'] == pytest.approx(
        4 * one['snapshot_bytes_per_s'])
    assert one['snapshot_wire_bytes'] == dense          # f32: raw
    assert one['dcn_link_frac'] > 0
    # misses drive row traffic: a perfect cache costs zero row bytes
    hot = serve_wire_cost(dense, qps=100.0, rows_per_query=64,
                          row_bytes=256, row_cache_hit_rate=1.0)
    cold = serve_wire_cost(dense, qps=100.0, rows_per_query=64,
                           row_bytes=256, row_cache_hit_rate=0.0)
    assert hot['row_bytes_per_s'] == 0.0
    assert cold['row_bytes_per_s'] == pytest.approx(100 * 64 * 256)
    # the int8 tier shrinks the pull ~4x, never below 1/4 + header
    i8 = serve_wire_cost(dense, compressor='Int8RingCompressor')
    assert dense / 4 <= i8['snapshot_wire_bytes'] < dense / 3.8


def test_simulate_cli_serving_block():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'simulate.py'),
         '--model', 'tinylm', '--json', '--serve-replicas', '2',
         '--serve-qps', '100', '--serve-wire', 'bf16'],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    srv = rec['serving']
    assert srv['replicas'] == 2 and srv['wire'] == 'bf16'
    assert 0 < srv['dcn_link_frac'] < 1
    assert srv['serve_bytes_per_s'] >= srv['snapshot_bytes_per_s']
