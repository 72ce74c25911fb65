"""Topology-aware hierarchical collectives (ISSUE 9): the two-tier
cost model and the shared per-bucket decision, numeric exactness of the
two-level emission vs the flat ring across dtypes and compressors
(including the int8 bucket path), the static==traced pin extended to
hierarchical emission, per-tier calibration, and the parse-time
Topology bandwidth guard."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import AXIS_DATA
from autodist_tpu.frontend import graph as fe
from autodist_tpu.parallel.axes import shard_map
from autodist_tpu.parallel.mesh import data_axis_node_groups
from autodist_tpu.parallel.plan import (ExecutionPlan, ShardedGrad,
                                        static_collective_schedule)
from autodist_tpu.resource_spec import ResourceSpec, Topology
from autodist_tpu.simulator import calibrate, search
from autodist_tpu.simulator.cost_model import (
    CostModelParams, choose_hierarchical, collective_time,
    hierarchical_time, num_node_groups, predict)
from autodist_tpu.strategy import AllReduce
from autodist_tpu.strategy.adapter import (FunctionalModel,
                                           PytreeGraphItem)

MiB = 1 << 20


def make_gi(shapes, dtype=jnp.float32):
    def init_fn(rng):
        return {k: jnp.zeros(s, dtype) for k, s in shapes.items()}
    return PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))


def make_rs(n=8, nodes=1):
    node_list = []
    for i in range(nodes):
        node = {'address': 'host%d' % i, 'cpus': [0],
                'network_bandwidth': 100,
                'gpus': list(range(n // nodes))}
        if i == 0:
            node['chief'] = True
        node_list.append(node)
    return ResourceSpec(resource_info={'nodes': node_list})


# -- cost model: the two-tier formula and the shared decision -------------

def test_hierarchical_time_degenerates_to_flat():
    p = CostModelParams()
    # nodes=1: pure-ICI ring, exactly the flat formula at the ICI link
    assert hierarchical_time(4 * MiB, 8, 1, p) == pytest.approx(
        collective_time('all_reduce', 4 * MiB, 8,
                        p.alpha_ici_s, p.beta_ici_s_per_byte))
    assert hierarchical_time(4 * MiB, 1, 1, p) == 0.0


def test_hierarchical_time_golden_two_node():
    # 4 MiB over n=8, k=2 (g=4): 2*3 ICI hops + 2*(3/4)*B ICI bytes,
    # 2*1 DCN hops + 2*(1/2)*(B/4) DCN bytes, + boundary pass
    p = CostModelParams()
    B = 4 * MiB
    expect = (2 * 3 * p.alpha_ici_s +
              2 * 3 / 4 * B * p.beta_ici_s_per_byte +
              2 * 1 * p.alpha_dcn_s +
              2 * 1 / 2 * (B / 4) * p.beta_dcn_s_per_byte +
              B * p.hier_boundary_s_per_byte)
    assert hierarchical_time(B, 8, 2, p) == pytest.approx(expect,
                                                          rel=1e-12)


def test_choose_hierarchical_flips_on_topology():
    p = CostModelParams()   # default: fast ICI, slow DCN
    # a large DCN-bound bucket on 2 nodes: two-level wins
    assert choose_hierarchical(4 * MiB, 'float32', None, 8, 2, p)
    # single node / non-dividing / one-device groups: flat stays
    assert not choose_hierarchical(4 * MiB, 'float32', None, 8, 1, p)
    assert not choose_hierarchical(4 * MiB, 'float32', None, 8, 3, p)
    assert not choose_hierarchical(4 * MiB, 'float32', None, 8, 8, p)
    # forced RING spec is an explicit flat-ring request
    assert not choose_hierarchical(4 * MiB, 'float32', None, 8, 2, p,
                                   spec='RING')
    # knob overrides
    assert not choose_hierarchical(4 * MiB, 'float32', None, 8, 2, p,
                                   knob='never')
    assert choose_hierarchical(16, 'float32', None, 8, 2, p,
                               knob='always')
    # a topology whose "DCN" matches ICI (single fat switch): the
    # two extra phases buy nothing and the boundary pass tips flat
    flat_p = CostModelParams(
        alpha_dcn_s=CostModelParams().alpha_ici_s,
        beta_dcn_s_per_byte=CostModelParams().beta_ici_s_per_byte)
    assert not choose_hierarchical(4 * MiB, 'float32', None, 8, 2,
                                   flat_p)


def test_num_node_groups_from_replica_hosts():
    gi = make_gi({'w': (64, 64)})
    s2 = AllReduce().build(gi, make_rs(8, nodes=2))
    assert num_node_groups(s2, None, 8) == 2
    s1 = AllReduce().build(gi, make_rs(8, nodes=1))
    assert num_node_groups(s1, None, 8) == 1
    # non-dividing replica count degrades to flat
    assert num_node_groups(s2, None, 7) == 1


def test_num_node_groups_requires_equal_per_host_split():
    """An UNEQUAL node shape (3+1 devices) must price flat: the mesh's
    group inference refuses unequal groups, so pricing a two-level
    schedule here would be exactly the predicted-vs-traced drift the
    shared decision exists to prevent."""
    gi = make_gi({'w': (64, 64)})
    rs = ResourceSpec(resource_info={'nodes': [
        {'address': 'host0', 'chief': True, 'cpus': [0],
         'gpus': [0, 1, 2], 'network_bandwidth': 100},
        {'address': 'host1', 'cpus': [0], 'gpus': [0],
         'network_bandwidth': 100}]})
    s = AllReduce().build(gi, rs)
    assert num_node_groups(s, None, 4) == 1
    rep = predict(AllReduce(hierarchical='auto').build(gi, rs), gi,
                  rs, num_replicas=4)
    assert all(b['hier'] == 0 for b in rep.breakdown)


def test_num_node_groups_honors_forced_override(monkeypatch):
    """AUTODIST_HIERARCHY_NODES must reach PRICING the same way it
    reaches the traced emission, or predicted and traced schedules
    drift on exactly the configuration the override exists for (a
    virtual CPU mesh given node structure for tests/benches)."""
    monkeypatch.setenv('AUTODIST_HIERARCHY_NODES', '2')
    gi = make_gi({'w': (1024, 1024)})
    rs1 = make_rs(8, nodes=1)   # single-node spec, forced 2 groups
    s = AllReduce().build(gi, rs1)
    assert num_node_groups(s, None, 8) == 2
    rep = predict(s, gi, rs1, num_replicas=8)
    assert rep.breakdown[0]['hier'] == 2
    # a non-dividing override degrades to flat, like the mesh side
    monkeypatch.setenv('AUTODIST_HIERARCHY_NODES', '3')
    assert num_node_groups(s, None, 8) == 1


def test_int8_hierarchical_prices_ici_at_raw_bytes():
    """The int8 schedule quantizes only at the tier boundary: its ICI
    phases move the full f32 payload, so pricing them at the int8 wire
    would underprice ~4x. With an ICI link only 2x faster than DCN the
    raw-byte ICI cost must flip the int8 decision to flat while the
    uncompressed bucket still goes hierarchical."""
    base = CostModelParams()
    p = CostModelParams(
        alpha_ici_s=base.alpha_dcn_s,
        beta_ici_s_per_byte=base.beta_dcn_s_per_byte / 2,
        alpha_dcn_s=base.alpha_dcn_s,
        beta_dcn_s_per_byte=base.beta_dcn_s_per_byte)
    B = 4 * MiB
    assert choose_hierarchical(B, 'float32', None, 8, 2, p)
    assert not choose_hierarchical(B, 'float32', 'Int8RingCompressor',
                                   8, 2, p)
    # and the time formula itself is monotone in the ICI byte count
    assert hierarchical_time(B // 4, 8, 2, p, ici_bytes=B) > \
        hierarchical_time(B // 4, 8, 2, p)


def test_predict_ranks_hierarchical_above_flat_ring_on_two_nodes():
    """ISSUE 9 acceptance: on a simulated 2-node topology the cost
    model ranks the hierarchical schedule above the flat ring for
    large DCN-bound buckets, and at/below it on single-node ICI."""
    gi = make_gi({'w': (1024, 1024)})
    rs2 = make_rs(8, nodes=2)
    hier = predict(AllReduce(hierarchical='always').build(gi, rs2),
                   gi, rs2, num_replicas=8)
    flat = predict(AllReduce(all_reduce_spec='RING').build(gi, rs2),
                   gi, rs2, num_replicas=8)
    assert hier.breakdown[0]['hier'] == 2
    assert flat.breakdown[0]['hier'] == 0
    assert hier.predicted_step_time_s < flat.predicted_step_time_s
    # single node: the hierarchical candidate degenerates to the SAME
    # flat schedule (identical time), and the ranked tie breaks to the
    # flat-named candidate
    rs1 = make_rs(8, nodes=1)
    h1 = predict(AllReduce(hierarchical='always').build(gi, rs1),
                 gi, rs1, num_replicas=8)
    f1 = predict(AllReduce().build(gi, rs1), gi, rs1, num_replicas=8)
    assert h1.breakdown[0]['hier'] == 0
    assert h1.predicted_step_time_s == pytest.approx(
        f1.predicted_step_time_s)
    feasible, _ = search.rank(gi, rs1)
    names = [c.name for c in feasible]
    assert names.index('AllReduce(chunk=128)') < \
        names.index('AllReduce(hierarchical)')


def test_rank_two_nodes_hierarchical_beats_flat_control():
    gi = make_gi({'w': (1024, 1024)})
    feasible, _ = search.rank(gi, make_rs(8, nodes=2))
    by_name = {c.name: c for c in feasible}
    assert by_name['AllReduce(hierarchical)'] \
        .report.predicted_step_time_s < \
        by_name['AllReduce(flat-only)'].report.predicted_step_time_s
    assert by_name['AllReduce(hierarchical)'] \
        .report.predicted_step_time_s < \
        by_name['AllReduce(RING)'].report.predicted_step_time_s


# -- node-group inference -------------------------------------------------

def test_data_axis_node_groups_forced_and_degenerate():
    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    assert data_axis_node_groups(mesh, forced_nodes=2) == \
        [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert data_axis_node_groups(mesh, forced_nodes=4) == \
        [[0, 1], [2, 3], [4, 5], [6, 7]]
    # 8 % 3 != 0 and g=1 are both degenerate
    assert data_axis_node_groups(mesh, forced_nodes=3) is None
    assert data_axis_node_groups(mesh, forced_nodes=8) is None
    # single process on CPU: no real node structure either
    assert data_axis_node_groups(mesh) is None


# -- emission: numeric exactness vs flat across dtypes/compressors --------

def _sync_outputs(gi, strategy, grads, mesh):
    plan = ExecutionPlan(strategy, gi, mesh)
    sources = list(gi.trainable_var_op_to_var.values())

    def sync(*gs):
        out = plan.sync_gradients(sources, list(gs), fe.Env({}, {}))
        return tuple(o.value if isinstance(o, ShardedGrad) else o
                     for o in out)

    f = jax.jit(shard_map(sync, mesh,
                                 tuple(P() for _ in grads),
                                 tuple(P() for _ in grads)))
    return [np.asarray(o) for o in f(*grads)], plan


@pytest.mark.parametrize('dtype,compressor', [
    (jnp.float32, 'NoneCompressor'),
    (jnp.bfloat16, 'NoneCompressor'),
    (jnp.float32, 'HorovodCompressor'),
])
def test_hierarchical_bit_identical_vs_flat(monkeypatch, dtype,
                                            compressor):
    """Two-level emission is a pure re-association of the same sum:
    with exactly-representable per-element sums (small integers) the
    result is BIT-identical to the flat ring, for the plain f32 wire,
    a bf16 tensor dtype, and the bf16 cast wire."""
    monkeypatch.setenv('AUTODIST_HIERARCHY_NODES', '2')
    shapes = {'v%02d' % i: (64, 48) for i in range(5)}
    gi = make_gi(shapes, dtype=dtype)
    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    rng = np.random.RandomState(0)
    # integers in [-8, 8): sums over 8 replicas stay exactly
    # representable in bf16 (<= 64) and trivially in f32
    grads = [jnp.asarray(rng.randint(-8, 8, s)).astype(dtype)
             for s in shapes.values()]
    rs = make_rs(8)
    flat_out, flat_plan = _sync_outputs(
        gi, AllReduce(chunk_size=2, compressor=compressor,
                      hierarchical='never').build(gi, rs), grads, mesh)
    hier_out, hier_plan = _sync_outputs(
        gi, AllReduce(chunk_size=2, compressor=compressor,
                      hierarchical='always').build(gi, rs), grads, mesh)
    assert all(b['hier'] == 0 for b in flat_plan.last_bucket_stats)
    assert all(b['hier'] == 2 for b in hier_plan.last_bucket_stats)
    for a, b in zip(flat_out, hier_out):
        assert a.dtype == b.dtype
        assert (a == b).all()


def test_hierarchical_int8_bucket_exact_on_block_constant(monkeypatch):
    """The int8 bucket path composes: quantize once, requantize at the
    tier boundary. With constant-valued gradients every block
    quantizes exactly at every stage, so flat-int8, hierarchical-int8
    and the uncompressed mean all agree to f32 exactness."""
    monkeypatch.setenv('AUTODIST_HIERARCHY_NODES', '2')
    shapes = {'v%02d' % i: (32, 32) for i in range(4)}
    gi = make_gi(shapes)
    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    grads = [jnp.full(s, float(i + 1), jnp.float32)
             for i, s in enumerate(shapes.values())]
    rs = make_rs(8)
    outs = {}
    for key, knob, comp_name in (
            ('f32', 'never', 'NoneCompressor'),
            ('flat8', 'never', 'Int8RingCompressor'),
            ('hier8', 'always', 'Int8RingCompressor')):
        outs[key], plan = _sync_outputs(
            gi, AllReduce(chunk_size=2, compressor=comp_name,
                          hierarchical=knob).build(gi, rs),
            grads, mesh)
        if key == 'hier8':
            assert all(b['hier'] == 2
                       for b in plan.last_bucket_stats)
            assert all(b['compressor'] == 'Int8RingCompressor'
                       for b in plan.last_bucket_stats)
    for key in ('flat8', 'hier8'):
        for a, b in zip(outs['f32'], outs[key]):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    # and the two int8 schedules agree with each other bit-for-bit
    for a, b in zip(outs['flat8'], outs['hier8']):
        assert (a == b).all()


def test_hierarchical_int8_within_compressor_bound(monkeypatch):
    """Random gradients: the hierarchical int8 path stays within the
    SAME error class as the flat int8 ring (one block-quantization
    roundtrip per tier boundary) — compared against the exact f32
    mean, both sit well inside the per-block scale bound."""
    monkeypatch.setenv('AUTODIST_HIERARCHY_NODES', '2')
    # an EVEN var count: chunk_size=2 packs pairs, and a lone int8
    # bucket needs real aux-state (error-feedback residuals) this
    # trace-only env does not carry
    shapes = {'v%02d' % i: (64, 64) for i in range(4)}
    gi = make_gi(shapes)
    mesh = Mesh(np.asarray(jax.devices()), (AXIS_DATA,))
    rng = np.random.RandomState(7)
    grads = [jnp.asarray(rng.randn(*s).astype('f4'))
             for s in shapes.values()]
    rs = make_rs(8)
    exact, _ = _sync_outputs(
        gi, AllReduce(chunk_size=2).build(gi, rs), grads, mesh)
    errs = {}
    for knob in ('never', 'always'):
        out, _ = _sync_outputs(
            gi, AllReduce(chunk_size=2,
                          compressor='Int8RingCompressor',
                          hierarchical=knob).build(gi, rs),
            grads, mesh)
        errs[knob] = max(np.abs(a - b).max()
                         for a, b in zip(exact, out))
        # absolute sanity: the quantization error is a few steps of
        # the largest PARTIAL-SUM block scale (pre-mean magnitude up
        # to n*|g|), divided back by n — a few |g|max/127 per tensor
        gmax = max(float(np.abs(np.asarray(g)).max()) for g in grads)
        assert errs[knob] <= 6 * gmax / 127.0 + 1e-6
    # same error CLASS: the boundary requantization may add a step or
    # two, never an order of magnitude
    assert errs['always'] <= 4 * errs['never'] + 1e-6


# -- static == traced, extended to hierarchical emission ------------------

def test_static_schedule_matches_traced_hierarchical(monkeypatch):
    monkeypatch.setenv('AUTODIST_HIERARCHY_NODES', '2')
    shapes = {'v%02d' % i: (128, 128) for i in range(6)}
    gi = make_gi(shapes)
    rs = make_rs(8)
    strategy = AllReduce(chunk_size=2).build(gi, rs)

    static = [e for e in static_collective_schedule(
        strategy, gi, 8, nodes=2) if e['phase'] == 'grad']

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
    jax.eval_shape(f, *grads)
    traced = plan.last_bucket_stats
    assert [(e['bytes'], e['members'], e['hier']) for e in static] == \
        [(e['bytes'], e['members'], e.get('hier', 0)) for e in traced]
    # the auto decision actually went hierarchical for these buckets
    assert any(e['hier'] == 2 for e in static)


# -- per-tier calibration -------------------------------------------------

def _tiered_row(kind, nbytes, seconds, groups, count=3):
    name = ('%%%s.1 = f32[%d]{0} %s(f32[%d]{0} %%p), '
            'replica_groups={%s}'
            % (kind, nbytes // 4, kind, nbytes // 4,
               ','.join('{%s}' % ','.join(map(str, g))
                        for g in groups)))
    return (name, seconds * count * 1e9, count)


def test_replica_groups_parsing():
    row = _tiered_row('all-reduce', 4096, 1e-5,
                      [[0, 1, 2, 3], [4, 5, 6, 7]])
    assert calibrate._replica_groups(row[0]) == \
        [[0, 1, 2, 3], [4, 5, 6, 7]]
    # the global group ({} or absent) parses as None
    assert calibrate._replica_groups(
        'f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}') is None


def test_calibration_fits_tiers_separately():
    """A hierarchical run's timeline carries intra-node rows (groups
    within one node) and cross-node rows; per-tier calibration must
    recover each tier's OWN constants."""
    a_i, b_i = 2e-6, 2e-11
    a_d, b_d = 40e-6, 6e-9
    intra = [[0, 1, 2, 3], [4, 5, 6, 7]]
    inter = [[r, r + 4] for r in range(4)]
    rows = []
    for nbytes in (1 << 16, 1 << 20, 1 << 24):
        t = collective_time('all_reduce', nbytes, 4, a_i, b_i)
        rows.append(_tiered_row('all-reduce', nbytes, t, intra))
        t = collective_time('all_reduce', nbytes, 2, a_d, b_d)
        rows.append(_tiered_row('all-reduce', nbytes, t, inter))
    params = calibrate.calibrate_from_timeline(
        CostModelParams(), rows, num_replicas=8, devices_per_node=4)
    assert params.calibrated
    assert params.alpha_ici_s == pytest.approx(a_i, rel=1e-3)
    assert params.beta_ici_s_per_byte == pytest.approx(b_i, rel=1e-3)
    assert params.alpha_dcn_s == pytest.approx(a_d, rel=1e-3)
    assert params.beta_dcn_s_per_byte == pytest.approx(b_d, rel=1e-3)


def test_calibration_tier_falls_back_to_shared_fit():
    """A tier with SOME rows but a degenerate fit (one byte size)
    borrows the group-aware shared fit; a tier ABSENT from the trace
    keeps its analytic constants — a flat-ring trace (all-DCN rows)
    must never overwrite the ICI tier with DCN-speed constants."""
    base = CostModelParams()
    a_i, b_i = 2e-6, 2e-11
    a_d, b_d = 40e-6, 6e-9
    intra = [[0, 1, 2, 3], [4, 5, 6, 7]]
    inter = [[r, r + 4] for r in range(4)]
    dcn_rows = []
    for nbytes in (1 << 16, 1 << 20, 1 << 24):
        t = collective_time('all_reduce', nbytes, 2, a_d, b_d)
        dcn_rows.append(_tiered_row('all-reduce', nbytes, t, inter))
    # absent ICI tier: analytic ICI constants survive, DCN calibrates
    params = calibrate.calibrate_from_timeline(
        CostModelParams(), dcn_rows, num_replicas=8,
        devices_per_node=4)
    assert params.calibrated
    assert params.alpha_dcn_s == pytest.approx(a_d, rel=1e-3)
    assert params.alpha_ici_s == base.alpha_ici_s
    assert params.beta_ici_s_per_byte == base.beta_ici_s_per_byte
    # degenerate ICI tier (one byte size): borrows the shared fit,
    # whose value the fit function itself defines
    t = collective_time('all_reduce', 1 << 20, 4, a_i, b_i)
    ici_rows = [_tiered_row('all-reduce', 1 << 20, t, intra)]
    rows = ici_rows + dcn_rows
    ici, dcn = calibrate.tiered_samples_from_timeline(rows, 4)
    expected = calibrate.fit_alpha_beta(ici + dcn, 8)
    params = calibrate.calibrate_from_timeline(
        CostModelParams(), rows, num_replicas=8, devices_per_node=4)
    assert params.calibrated
    assert params.alpha_ici_s == pytest.approx(expected[0], rel=1e-9)
    assert params.beta_ici_s_per_byte == pytest.approx(expected[1],
                                                       rel=1e-9)


def test_calibration_without_devices_per_node_unchanged():
    """The legacy single-fit path is untouched when no node shape is
    given."""
    alpha, beta = 5e-6, 4e-11
    rows = []
    for nbytes in (1 << 16, 1 << 20, 1 << 24):
        t = collective_time('all_reduce', nbytes, 8, alpha, beta)
        rows.append((
            '%%all-reduce.1 = f32[%d]{0} all-reduce(f32[%d]{0} %%p), '
            'replica_groups={}' % (nbytes // 4, nbytes // 4),
            t * 3e9, 3))
    params = calibrate.calibrate_from_timeline(
        CostModelParams(), rows, num_replicas=8)
    assert params.alpha_ici_s == pytest.approx(alpha, rel=1e-3)


# -- Topology guard: resolved link constants must be positive finite ------

@pytest.mark.parametrize('field,val', [
    ('ici_bandwidth_gbps', float('nan')),
    ('dcn_bandwidth_gbps', float('nan')),
    ('ici_latency_us', float('inf')),
])
def test_topology_rejects_non_finite_resolved_values(field, val):
    """NaN slips past the raw positivity check (NaN <= 0 is False);
    the resolved-value guard names the offending field — the simulator
    divides by link() bandwidth with no guard of its own."""
    with pytest.raises(ValueError, match='topology.%s' % field):
        make_rs(4, nodes=1).__class__(resource_info={
            'nodes': [{'address': 'h', 'chief': True, 'cpus': [0],
                       'gpus': [0, 1], 'network_bandwidth': 100}],
            'topology': {field: val}})


def test_topology_guard_direct_construction():
    from autodist_tpu.resource_spec import DeviceType
    with pytest.raises(ValueError, match='dcn_bandwidth_gbps'):
        Topology({'dcn_bandwidth_gbps': float('nan')},
                 DeviceType.TPU, 1, multi_node=True)
    # defaults stay valid
    t = Topology({}, DeviceType.TPU, 1, multi_node=False)
    assert t.link(cross_node=True)[0] > 0
