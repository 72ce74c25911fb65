"""Device-plane roofline observatory (ISSUE 15).

Covers: Topology peak-table validation, cost/memory analysis
degradation (a CPU-fallback record is well-formed with an explicit
null MFU, never a raise), schedule entry-id round-trip between the
traced emission and the static schedule, the per-entry drift join,
the entry-labeled calibration fit the old unlabeled classification
gets wrong (pinned), the tracker's MFU-regression flight events, the
monitor's compute/memory-bound verdict refinement, the
silent-empty-timeline mismatch logging, and the roofline CLI smoke.
"""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from autodist_tpu.resource_spec import (DEVICE_KINDS,  # noqa: E402
                                        ResourceSpec, peak_flops_for_kind)
from autodist_tpu.telemetry import roofline as rl  # noqa: E402


def _spec(topology=None, gpus=8):
    info = {'nodes': [{'address': 'localhost', 'chief': True,
                       'cpus': [0], 'gpus': list(range(gpus)),
                       'network_bandwidth': 100}]}
    if topology is not None:
        info['topology'] = topology
    return ResourceSpec(resource_info=info)


# -- Topology peak table ---------------------------------------------------

@pytest.mark.parametrize('kind', ['v5e', 'TPU v5 lite'])
def test_topology_peak_defaults_per_kind(kind):
    """The short alias and the string JAX really reports for a v5e
    (``jax.devices()[0].device_kind``) both take the v5e row."""
    flops, hbm = DEVICE_KINDS['tpu v5 lite'][:2]
    assert (flops, hbm) == (197e12, 819.0)
    topo = _spec({'device_kind': kind}).topology
    assert topo.peak_flops == flops
    assert topo.peak_hbm_gbps == hbm
    assert topo.peaks() == (flops, hbm * 1e9)
    assert peak_flops_for_kind(kind) == flops


def test_no_peak_is_assumed_for_an_unknown_tpu():
    """An unknown TPU ``device_kind`` raises (no nearest-row guess),
    and a TPU spec that names no kind has NO peak, not the v5e's."""
    for kind in ('TPU v9', 'tpu v5', 'v5'):
        with pytest.raises(ValueError, match='device table'):
            peak_flops_for_kind(kind)
    assert peak_flops_for_kind('cpu') is None
    unnamed = ResourceSpec(resource_info={'nodes': [{
        'address': 'localhost', 'chief': True, 'tpus': [0, 1],
        'network_bandwidth': 100}]}).topology
    assert unnamed.peaks() == (None, None)


def test_topology_cpu_kind_resolves_to_none_peaks():
    topo = _spec({'device_kind': 'cpu'}).topology
    assert topo.peak_flops is None and topo.peak_hbm_gbps is None
    assert topo.peaks() == (None, None)


def test_topology_explicit_peaks_override_table():
    topo = _spec({'device_kind': 'v5e', 'peak_flops': 1e14,
                  'peak_hbm_gbps': 500}).topology
    assert topo.peak_flops == 1e14
    assert topo.peak_hbm_gbps == 500.0


def test_topology_rejects_nonpositive_peak_naming_field():
    with pytest.raises(ValueError, match='peak_flops'):
        _spec({'peak_flops': 0})
    with pytest.raises(ValueError, match='peak_hbm_gbps'):
        _spec({'peak_hbm_gbps': -3})


def test_topology_rejects_nan_peak_naming_field():
    with pytest.raises(ValueError, match='peak_flops'):
        _spec({'peak_flops': float('nan')})


def test_topology_rejects_unknown_device_kind():
    with pytest.raises(ValueError, match='device_kind'):
        _spec({'device_kind': 'abacus9000'})


def test_env_peak_override_wins(monkeypatch):
    monkeypatch.setenv('AUTODIST_ROOFLINE_PEAKS',
                       'flops=2e14,hbm_gbps=1000')
    pf, ph = _spec({'device_kind': 'v5e'}).topology.peaks()
    assert pf == 2e14 and ph == 1e12


def test_env_peak_override_validated_at_parse(monkeypatch):
    from autodist_tpu.const import ENV
    monkeypatch.setenv('AUTODIST_ROOFLINE_PEAKS', 'flops=-1')
    with pytest.raises(ValueError, match='AUTODIST_ROOFLINE_PEAKS'):
        ENV.AUTODIST_ROOFLINE_PEAKS.val
    monkeypatch.setenv('AUTODIST_ROOFLINE_PEAKS', 'watts=9')
    with pytest.raises(ValueError, match='AUTODIST_ROOFLINE_PEAKS'):
        ENV.AUTODIST_ROOFLINE_PEAKS.val
    monkeypatch.setenv('AUTODIST_ROOFLINE_PEAKS', 'hbm_gbps=819')
    assert ENV.AUTODIST_ROOFLINE_PEAKS.val == {'hbm_gbps': 819.0}


# -- cost/memory analysis degradation --------------------------------------

class _NoAnalysis:
    def cost_analysis(self):
        raise NotImplementedError('backend does not report')

    def memory_analysis(self):
        raise NotImplementedError('backend does not report')


class _WithCost:
    calls = 0

    def cost_analysis(self):
        type(self).calls += 1
        return {'flops': 1e9, 'bytes accessed': 2e8}


def test_cost_of_degrades_to_none_never_raises():
    cost = rl.cost_of(_NoAnalysis())
    assert cost == {'flops': None, 'bytes_accessed': None}
    assert rl.memory_of(_NoAnalysis()) is None


def test_cost_of_cached_per_program():
    prog = _WithCost()
    a = rl.cost_of(prog)
    b = rl.cost_of(prog)
    assert a == b == {'flops': 1e9, 'bytes_accessed': 2e8}
    assert _WithCost.calls == 1


def test_classify_regime_cpu_fallback_is_well_formed():
    rec = rl.classify_regime(None, None, 0.1, None, None)
    assert rec['mfu'] is None
    assert 'cost_analysis' in rec['mfu_null_reason'] or \
        'peak' in rec['mfu_null_reason']
    assert rec['roofline_regime'] is None and rec['regime_reason']


def test_classify_regime_picks_dominant_bound():
    # compute-bound: flops fraction dominates
    rec = rl.classify_regime(9e13, 1e9, 1.0, 1e14, 1e12)
    assert rec['roofline_regime'] == 'compute'
    assert rec['mfu'] == pytest.approx(0.9)
    # memory-bound: bytes fraction dominates
    rec = rl.classify_regime(1e12, 8e11, 1.0, 1e14, 1e12)
    assert rec['roofline_regime'] == 'memory'
    # comms-bound: exposed wire dominates the wall
    rec = rl.classify_regime(1e12, 1e9, 1.0, 1e14, 1e12, comms_s=0.9)
    assert rec['roofline_regime'] == 'comms'


def test_tracker_records_mfu_regression_flight_event():
    from autodist_tpu.telemetry.core import Telemetry
    from autodist_tpu.telemetry.flight import FlightRecorder
    tel = Telemetry(enabled=False)
    flight = FlightRecorder(capacity=64)
    tr = rl.RooflineTracker(peak_flops=1e14, peak_hbm_bps=1e12,
                            every=1, tel=tel, flight=flight,
                            worker='p7')
    cost = {'flops': 5e13, 'bytes_accessed': 1e9}
    for s in range(1, 7):
        tr.observe_step(s, 1.0, cost=cost)      # mfu 0.5 baseline
    rec = tr.observe_step(7, 4.0, cost=cost)    # mfu 0.125 -> cliff
    assert rec['mfu'] == pytest.approx(0.125)
    assert tr.regressions == 1
    kinds = [e['kind'] for e in flight.events()]
    assert 'mfu_regression' in kinds
    ev = [e for e in flight.events() if e['kind'] == 'mfu_regression'][0]
    assert ev['worker'] == 'p7' and ev['step'] == 7


def test_memory_drift_classes_and_unavailable_path():
    est = {'params_bytes': 100, 'grads_bytes': 50,
           'optimizer_bytes': 200, 'bucket_staging_bytes': 50,
           'total_bytes': 400}
    out = rl.memory_drift(None, est)
    assert out['available'] is False and out['drift_ratio'] is None
    assert 'reason' in out
    measured = {'argument_size_in_bytes': 330,
                'temp_size_in_bytes': 80, 'live_bytes': 410}
    out = rl.memory_drift(measured, est)
    assert out['available'] is True
    assert out['classes']['state']['drift_ratio'] == \
        pytest.approx(330 / 300, abs=1e-3)
    assert out['classes']['transient']['drift_ratio'] == \
        pytest.approx(80 / 100, abs=1e-3)
    assert out['drift_ratio'] == pytest.approx(410 / 400, abs=1e-3)


# -- entry ids + the drift join --------------------------------------------

def _bucketed_plan(n_vars=6, dim=64, chunk=2):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.const import AXIS_DATA
    from autodist_tpu.frontend import graph as fe
    from autodist_tpu.parallel.axes import shard_map
    from autodist_tpu.parallel.plan import ExecutionPlan, ShardedGrad
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.adapter import (FunctionalModel,
                                               PytreeGraphItem)

    devs = jax.devices()

    def init_fn(rng):
        return {'v%02d' % i: jnp.zeros((dim, dim), jnp.float32)
                for i in range(n_vars)}

    gi = PytreeGraphItem(FunctionalModel(init_fn, lambda p, b: 0.0))
    rs = _spec(gpus=len(devs))
    strategy = AllReduce(chunk_size=chunk).build(gi, rs)
    mesh = Mesh(np.asarray(devs), (AXIS_DATA,))
    plan = ExecutionPlan(strategy, gi, mesh)
    sources = list(gi.trainable_var_op_to_var.values())
    grads = [jnp.ones((dim, dim), jnp.float32) for _ in sources]

    def sync(*gs):
        out = plan.sync_gradients(sources, list(gs), fe.Env({}, {}))
        return tuple(o.value if isinstance(o, ShardedGrad) else o
                     for o in out)

    f = jax.jit(shard_map(sync, mesh,
                                 tuple(P() for _ in grads),
                                 tuple(P() for _ in grads)))
    jax.block_until_ready(f(*grads))
    return plan, strategy, gi, len(devs)


def test_entry_ids_roundtrip_traced_to_static():
    from autodist_tpu.parallel.plan import static_collective_schedule
    plan, strategy, gi, n = _bucketed_plan()
    traced = plan.last_bucket_stats
    assert traced, 'bucketed sync emitted nothing'
    assert all(e.get('entry_id') for e in traced)
    static = static_collective_schedule(strategy, gi, n)
    static_by_id = {e['entry_id']: e for e in static}
    for e in traced:
        assert e['entry_id'] in static_by_id, e['entry_id']
        s = static_by_id[e['entry_id']]
        # the id maps back to the SAME entry: kind and bytes agree
        assert s['kind'] == e['kind'] and s['bytes'] == e['bytes']
        assert s['members'] == e['members']


def test_entry_ids_distinguish_identical_chunks():
    from autodist_tpu.parallel.plan import assign_entry_ids
    entries = [{'kind': 'psum_scatter', 'dtype': 'float32',
                'compressor': None, 'bytes': 1024, 'members': ['w']}
               for _ in range(3)]
    assign_entry_ids(entries)
    ids = [e['entry_id'] for e in entries]
    assert len(set(ids)) == 3
    assert ids[1].endswith('#1') and ids[2].endswith('#2')


def _ar_timeline(schedule, n, alpha, beta, multi_node=False):
    """Synthetic HLO timeline rows priced at known (α, β) for every
    expected sub-collective of the schedule."""
    rows = []
    for i, e in enumerate(schedule):
        for hk, result_b, _tier, grp, full_b in rl.expected_subrows(
                e, n, multi_node=multi_node):
            hops = (2 if hk == 'all-reduce' else 1) * (grp - 1)
            frac = (2.0 if hk == 'all-reduce' else 1.0) * \
                (grp - 1) / grp
            t = hops * alpha + frac * full_b * beta
            elems = max(1, result_b // 4)
            rows.append((
                '%%x.%d = f32[%d]{0} %s(f32[%d]{0} %%p0), '
                'replica_groups={}' % (i, elems, hk, elems),
                t * 1e9, 1))
    return rows


def test_drift_table_joins_and_reports_drift():
    from autodist_tpu.parallel.plan import (assign_entry_ids,
                                            static_collective_schedule)
    plan, strategy, gi, n = _bucketed_plan()
    schedule = static_collective_schedule(strategy, gi, n)
    alpha, beta = 2e-6, 1e-9
    rows = _ar_timeline(schedule, n, alpha, beta)
    table = rl.drift_table(schedule, rows, n)
    assert table['unmatched_rows'] == 0
    ids = {e['entry_id'] for e in schedule}
    for row in table['entries']:
        assert row['entry_id'] in ids
        assert row['achieved_s'] is not None
        assert row['drift_ratio'] > 0
    assert table['worst_drift_ratio'] is not None
    assert 'ici' in table['tiers']
    assert table['tiers']['ici']['achieved_bytes_per_s'] > 0


def test_drift_table_degrades_on_empty_timeline():
    from autodist_tpu.parallel.plan import static_collective_schedule
    plan, strategy, gi, n = _bucketed_plan()
    schedule = static_collective_schedule(strategy, gi, n)
    table = rl.drift_table(schedule, [], n)
    assert all(r['achieved_s'] is None for r in table['entries'])
    assert all(r.get('note') for r in table['entries'])
    assert table['worst_drift_ratio'] is None


def test_partial_join_tier_aggregate_covers_matched_rows_only():
    """A trace missing a joinable entry must not skew the tier view:
    achieved and predicted bytes/s cover the SAME matched row set, so
    a 1KB-only trace against a 1KB + 1MB schedule grades the link on
    the 1KB row alone instead of dividing its wire bytes by a
    predicted time that includes the unmatched megabyte."""
    def ar(nbytes, name):
        return {'kind': 'all_reduce', 'dtype': 'float32',
                'compressor': 'NoneCompressor', 'bytes': nbytes,
                'vars': 1, 'members': [name], 'phase': 'grad',
                'hier': 0, 'spec': 'AUTO', 'wus': False}

    n = 4
    schedule = [ar(1 << 10, 'small'), ar(1 << 20, 'big')]
    # trace carries ONLY the small entry's row
    rows = [('%%x = f32[256]{0} all-reduce(f32[256]{0} %%p0), '
             'replica_groups={}', 1e5, 1)]
    table = rl.drift_table(schedule, rows, n)
    small = [r for r in table['entries']
             if r['entry_id'].endswith('small+1')][0]
    big = [r for r in table['entries']
           if r['entry_id'].endswith('big+1')][0]
    assert small['achieved_s'] is not None
    assert big['achieved_s'] is None and 'no matching' in big['note']
    tier = table['tiers']['ici']
    assert tier['rows'] == 1
    # both sides of the ratio are the matched row: predicted bytes/s
    # equals the bare link model on the 1KB row, NOT a figure dragged
    # three orders of magnitude down by the unmatched megabyte
    from autodist_tpu.simulator.cost_model import CostModelParams
    moved, pred = rl._subrow_link_model('all-reduce', n, 1 << 10,
                                        'ici', CostModelParams())
    assert tier['wire_bytes'] == int(moved)
    assert tier['predicted_bytes_per_s'] == \
        pytest.approx(moved / pred, rel=1e-6)


def test_monitor_reset_baselines_clears_roofline_regimes():
    from autodist_tpu.telemetry.monitor import CohortMonitor
    mon = CohortMonitor(workers=['p0', 'p1'], warmup_steps=0)
    mon.observe_roofline('p1', {'roofline_regime': 'memory',
                                'mfu': 0.1})
    assert mon.snapshot()['roofline']
    mon.reset_baselines()
    assert mon.snapshot()['roofline'] == {}


def test_drift_table_marks_unjoinable_kinds():
    entries = [{'kind': 'sparse_all_gather', 'dtype': 'float32',
                'compressor': None, 'bytes': 4096, 'vars': 1,
                'members': ['emb'], 'phase': 'grad', 'hier': 0,
                'spec': 'AUTO', 'wus': False},
               {'kind': 'all_reduce', 'dtype': 'float32',
                'compressor': 'Int8RingCompressor', 'bytes': 4096,
                'vars': 1, 'members': ['w'], 'phase': 'grad',
                'hier': 0, 'spec': 'AUTO', 'wus': False}]
    table = rl.drift_table(entries, [], 2)
    for row in table['entries']:
        assert row['achieved_s'] is None
        assert 'joinable' in row['note']


def test_hier_entry_expands_to_two_tier_subrows():
    e = {'kind': 'all_reduce', 'dtype': 'float32',
         'compressor': 'NoneCompressor', 'bytes': 1 << 20,
         'members': ['w'], 'hier': 2, 'vars': 1, 'phase': 'grad',
         'spec': 'AUTO', 'wus': False}
    subs = rl.expected_subrows(e, 8, multi_node=True)
    assert [s[0] for s in subs] == ['reduce-scatter', 'all-reduce',
                                    'all-gather']
    assert {s[2] for s in subs} == {'ici', 'dcn'}


# -- the calibration pin: entry-labeled beats unlabeled --------------------

def test_entry_labeled_fit_fixes_reduce_scatter_beta():
    """The unlabeled path feeds a reduce-scatter's HLO RESULT shape
    (the 1/n shard) into a cost shape priced over the FULL buffer, so
    its fitted β is inflated ~n-fold; the entry-labeled samples carry
    the schedule's full bytes and recover the true β. This is the fit
    the old classification demonstrably gets wrong."""
    from autodist_tpu.simulator.calibrate import (
        calibrate_from_drift, calibrate_from_timeline, fit_alpha_beta,
        samples_from_timeline)
    from autodist_tpu.simulator.cost_model import CostModelParams

    n = 4
    alpha, beta = 1e-6, 2e-9
    schedule = []
    for i, nbytes in enumerate((1 << 18, 1 << 20, 1 << 22)):
        schedule.append({'kind': 'psum_scatter', 'dtype': 'float32',
                         'compressor': None, 'bytes': nbytes,
                         'vars': 1, 'members': ['w%d' % i],
                         'phase': 'grad', 'hier': 0, 'spec': 'AUTO',
                         'wus': False})
    rows = []
    for i, e in enumerate(schedule):
        full = e['bytes']
        t = (n - 1) * alpha + (n - 1) / n * full * beta
        elems = full // 4 // n          # the HLO RESULT: the 1/n shard
        rows.append((
            '%%rs.%d = f32[%d]{0} reduce-scatter(f32[%d]{0} %%p0), '
            'replica_groups={}' % (i, elems, elems * n), t * 1e9, 1))

    # OLD: unlabeled rows -> β inflated by ~n
    old = fit_alpha_beta(samples_from_timeline(rows), n)
    assert old is not None
    assert old[1] == pytest.approx(n * beta, rel=0.05)
    params_old = calibrate_from_timeline(CostModelParams(), rows, n)
    assert params_old.calibrated
    assert params_old.beta_ici_s_per_byte == \
        pytest.approx(n * beta, rel=0.05)

    # NEW: entry-labeled samples -> the true β
    table = rl.drift_table(schedule, rows, n)
    params_new = calibrate_from_drift(CostModelParams(), table, n)
    assert params_new.calibrated
    assert params_new.beta_ici_s_per_byte == \
        pytest.approx(beta, rel=0.05)
    assert params_old.beta_ici_s_per_byte > \
        3 * params_new.beta_ici_s_per_byte


# -- monitor refinement ----------------------------------------------------

def _step_records(worker, steps, wall):
    return [{'name': 'step', 't0': float(s), 'dur': wall,
             'worker': worker, 'tags': {'step': s, 'worker': worker}}
            for s in steps]


def test_monitor_refines_host_compute_with_roofline_regime():
    from autodist_tpu.telemetry.flight import FlightRecorder
    from autodist_tpu.telemetry.monitor import CohortMonitor
    mon = CohortMonitor(workers=['p0', 'p1', 'p2'], window=32,
                        warmup_steps=0, min_samples=3,
                        confirmations=1, policy='advise',
                        flight=FlightRecorder(capacity=64))
    steps = range(1, 9)
    mon.ingest(_step_records('p0', steps, 0.10))
    mon.ingest(_step_records('p2', steps, 0.10))
    mon.ingest(_step_records('p1', steps, 0.40))
    mon.observe_roofline('p1', {'roofline_regime': 'memory',
                                'mfu': 0.12, 'hbm_frac': 0.9,
                                'step': 8})
    verdicts = mon.update_verdicts()
    assert verdicts, 'expected a straggler verdict'
    v = [x for x in verdicts if x['worker'] == 'p1'][0]
    assert v['classification'] == 'memory_bound'
    assert v['roofline']['regime'] == 'memory'
    assert v['exclude_candidate'] is True
    snap = mon.snapshot()
    assert snap['roofline']['p1']['mfu'] == 0.12


def test_monitor_ingests_roofline_events_from_the_wire():
    from autodist_tpu.telemetry.monitor import CohortMonitor
    mon = CohortMonitor(workers=['p0', 'p1'], warmup_steps=0)
    mon.ingest([{'name': 'roofline', 't0': 1.0, 'worker': 'p1',
                 'tags': {'worker': 'p1', 'step': 4,
                          'roofline_regime': 'compute', 'mfu': 0.61}}])
    assert mon.snapshot()['roofline']['p1']['mfu'] == 0.61


def test_monitor_without_roofline_keeps_host_compute():
    from autodist_tpu.telemetry.flight import FlightRecorder
    from autodist_tpu.telemetry.monitor import CohortMonitor
    mon = CohortMonitor(workers=['p0', 'p1', 'p2'], warmup_steps=0,
                        min_samples=3, confirmations=1,
                        flight=FlightRecorder(capacity=64))
    steps = range(1, 9)
    mon.ingest(_step_records('p0', steps, 0.10))
    mon.ingest(_step_records('p2', steps, 0.10))
    mon.ingest(_step_records('p1', steps, 0.40))
    v = [x for x in mon.update_verdicts() if x['worker'] == 'p1'][0]
    assert v['classification'] == 'host_compute'
    assert 'roofline' not in v


# -- profiling silent-empty mismatch ---------------------------------------

def test_collective_timeline_logs_emitted_vs_empty_mismatch(
        tmp_path, monkeypatch):
    from autodist_tpu.utils import profiling
    calls = []
    monkeypatch.setattr(profiling.logging, 'warning',
                        lambda msg, *a: calls.append(msg % a))
    out = profiling.collective_timeline(str(tmp_path),
                                        expected_collectives=7)
    assert out == []
    assert any('7 collective(s)' in c for c in calls), calls
    # legacy quiet path: no expectation, only the generic trace warning
    calls.clear()
    out = profiling.collective_timeline(str(tmp_path))
    assert out == []
    assert not any('collective(s)' in c for c in calls), calls


def test_calibrate_from_trace_threads_expected_count(tmp_path,
                                                     monkeypatch):
    from autodist_tpu.simulator import calibrate
    from autodist_tpu.simulator.cost_model import CostModelParams
    seen = {}

    def fake_timeline(trace_dir, line_name='XLA Ops',
                      expected_collectives=0):
        seen['expected'] = expected_collectives
        return []

    import autodist_tpu.utils.profiling as profiling
    monkeypatch.setattr(profiling, 'collective_timeline',
                        fake_timeline)
    params = calibrate.calibrate_from_trace(
        CostModelParams(), str(tmp_path), 4, expected_collectives=3)
    assert seen['expected'] == 3
    assert not params.calibrated


# -- CLI -------------------------------------------------------------------

def test_roofline_cli_json_smoke(tmp_path):
    block = {
        'mfu': None,
        'mfu_null_reason': 'no peak-FLOPs table entry (test)',
        'memory': {'available': False, 'reason': 'test',
                   'drift_ratio': None},
        'drift': {'entries': [
            {'entry_id': 'all_reduce:float32:NoneCompressor:1024B:v+1',
             'kind': 'all_reduce', 'predicted_s': 1e-5,
             'achieved_s': 2e-5, 'drift_ratio': 2.0, 'tiers': ['ici']}],
            'tiers': {}, 'worst_drift_ratio': 2.0,
            'entry_ids_roundtrip': True},
    }
    path = tmp_path / 'roofline.json'
    path.write_text(json.dumps(block))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'roofline.py'),
         str(path), '--json'],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    parsed = json.loads(out.stdout)
    assert parsed['drift']['worst_drift_ratio'] == 2.0
    # human rendering too (no --json): mentions the null reason
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'roofline.py'),
         str(path)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert 'MFU: null' in out.stdout
    assert 'round-trip' in out.stdout


# -- session integration ---------------------------------------------------

def test_session_roofline_tracker_samples_steps(monkeypatch):
    monkeypatch.setenv('AUTODIST_ROOFLINE', '1')
    monkeypatch.setenv('AUTODIST_ROOFLINE_EVERY', '1')
    import autodist_tpu as ad
    from autodist_tpu import autodist as ad_mod
    ad_mod._DEFAULT_AUTODIST.clear()
    autodist = ad.AutoDist(
        resource_info={'nodes': [{'address': 'localhost',
                                  'chief': True, 'gpus': [0, 1],
                                  'network_bandwidth': 100}]},
        strategy_builder=ad.AllReduce(chunk_size=2))
    rng = np.random.RandomState(0)
    xs = rng.randn(8, 16).astype(np.float32)
    ys = rng.randn(8).astype(np.float32)
    with autodist.scope():
        w = ad.Variable(rng.randn(16, 1).astype(np.float32) * 0.1,
                        name='w')
        x = ad.placeholder(shape=[None, 16], dtype=np.float32,
                           name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        pred = ad.ops.reduce_mean(ad.ops.matmul(x, w), axis=1)
        loss = ad.ops.reduce_mean(ad.ops.square(pred - y))
        train = ad.optimizers.SGD(0.1).minimize(loss)
        sess = autodist.create_distributed_session()
        for _ in range(3):
            sess.run(train, feed_dict={x: xs, y: ys})
        tracker = sess._roofline_tracker
        assert tracker is not None
        assert tracker.samples >= 3
        rec = tracker.records[-1]
        assert rec['wall_s'] > 0
        # flops computed from the lowered step on the CPU backend
        assert rec['flops'] is None or rec['flops'] > 0
        assert 'roofline_regime' in rec
        sess.close()
