"""Family ``xing4`` (PR 48), the cell's side: the comparison that decides
``correct`` through the engine and the family as ``harness.py`` calls them
(``trainer_leaves_parked``: AdamW's slots off the device meanwhile), each
deliberate fault against the cell's own limits, and a rehearsal of the
cell's run loop on the CPU. The tiny configuration and the faults are
``test_benchmark_xing4.py``'s."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import benchmark_json
from test_benchmark_xing4 import (CELL, FAULTS, PEAKS, TRAFFIC, case,  # noqa
                                  reference_grads, tiny_config)

from benchmark import harness
from benchmark.models import xing4


@pytest.fixture(scope='module')
def probed(case):
    """The program's side of the cell's own comparison, once: the probe
    of ``trainer_leaves_parked`` on two sequences (loss, global norm, and
    the gradient it left for the family), AdamW's slots off the device
    meanwhile and back, bit for bit, for the first step."""
    from benchmark.engines import trainer_leaves, trainer_leaves_parked
    config, _, params, probe, _ = case
    probe = {k: v[:2] for k, v in probe.items()}
    engine = trainer_leaves_parked.Engine(xing4.build(config), {'dp': 1},
                                          jax.devices()[:1])
    # (a copy: the training steps below donate their state)
    state = engine.trainer.init(None, params=jax.tree.map(jnp.array, params))
    slots = jax.tree.map(np.asarray, state.opt_state)
    got = engine.loss_and_grad_norm(state, probe)
    assert set(trainer_leaves.PROBE) == {'gradients'}
    gradients = trainer_leaves.PROBE.pop('gradients')
    # parked: the slots' device buffers are gone, the parameters' are not
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state.opt_state))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(state.params))
    # ... and come back bit for bit, on their shardings, for the first step
    # (``fit`` calls this; the rehearsals below train through it)
    back = engine.restored(state)
    assert engine._parked is None and engine.restored(back) is back
    assert back.params is state.params
    for a, b in zip(jax.tree.leaves(back.opt_state), jax.tree.leaves(slots)):
        assert a.sharding.device_set == set(engine.devices)
        np.testing.assert_array_equal(np.asarray(a), b)
    return probe, got, gradients


@pytest.fixture(scope='module')
def wanted(case):
    """The sound reference's gradient on the case's probe, once."""
    config, _, params, probe, _ = case
    return reference_grads(config, xing4.to_reference_params(params),
                           probe)[1]


def pushed(grads, layer=1, share=0.05):
    """``grads`` (the reference's names) with ``b_post`` of an expert
    layer's MLP connection moved by ``share`` of that connection's
    ``phi_post``'s norm: far past the bias's own size, a twentieth on the
    connection's pooled scale."""
    mlp = {k: np.array(v, np.float64)
           for k, v in grads['layers']['hc_mlp'].items()}
    mlp['b_post'][layer] += share * np.linalg.norm(
        mlp['phi_post'][layer]) / 2.0            # four numbers: norm 1
    return dict(grads, layers=dict(grads['layers'], hc_mlp=mlp))


def through_the_harness(config, params, probed, **switches):
    """``(checks pass, reference)`` of the cell's own comparison: the
    family's reference beside the probe's gradient, under
    ``harness.close`` at the harness's limits."""
    from benchmark.engines import trainer_leaves
    probe, got, gradients = probed
    trainer_leaves.PROBE['gradients'] = gradients
    want = xing4.reference_loss_and_grad_norm(
        config, xing4.to_reference_params(params), probe, **switches)
    assert 'gradients' not in trainer_leaves.PROBE     # taken, not left
    return (harness.close(got[0], want[0], harness.LOSS_RTOL),
            harness.close(got[1], want[1], harness.GRAD_NORM_RTOL)), want


@pytest.mark.parametrize('broken,fails', [({}, False)] + [
    (FAULTS[name], True) for name in (
        'one_sinkhorn_round', 'h_post_without_its_2', 'q_without_its_norm',
        'scale_without_mscale_squared', 'yarn_off')] + [
    (dict(matmul_dtype=jnp.float8_e4m3fn), True)],
    ids=['sound', 'one_sinkhorn_round', 'h_post_without_its_2',
         'q_without_its_norm', 'scale_without_mscale_squared', 'yarn_off',
         'fp8_products'])
def test_the_cells_own_comparison_catches_each_fault(case, probed, capsys,
                                                     broken, fails):
    """Through the engine and the family as ``harness.py`` calls them, at
    the cell's own limits: the sound reference passes and a deliberate
    fault fails ``reference_grad_norm``."""
    config, _, params, _, _ = case
    (loss_ok, norm_ok), want = through_the_harness(config, params, probed,
                                                   **broken)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line['limits'] == {'leaf': xing4.LEAF_RTOL,
                              'routed_leaf': xing4.ROUTED_LEAF_RTOL,
                              'router_leaf': xing4.ROUTER_LEAF_RTOL,
                              'hc_leaf': xing4.HC_LEAF_RTOL,
                              'hc_mix_leaf': xing4.HC_MIX_LEAF_RTOL}
    leaves = line['gradient_leaves']
    # embed, ln_final, head; a layer's 8 of attention and 18 of its two
    # connections; the dense layer's 3 more, an expert layer's 7 of two
    assert len(leaves) == 3 + (26 + 3) + (26 + 7) * 2
    assert leaves['layers/b_select/0'] == leaves['layers/b_select/1'] == 0
    assert xing4.leaf_limit('layers/w_down/1') == xing4.ROUTED_LEAF_RTOL
    assert xing4.leaf_limit('layers/w_router/0') == xing4.ROUTER_LEAF_RTOL
    assert xing4.leaf_limit('layers/hc_mlp/phi_res/1') \
        == xing4.leaf_limit('dense/hc_attn/alpha_pre') \
        == xing4.HC_MIX_LEAF_RTOL
    assert xing4.leaf_limit('layers/hc_mlp/phi_post/1') \
        == xing4.leaf_limit('dense/hc_attn/alpha_post') == xing4.HC_LEAF_RTOL
    assert xing4.leaf_limit('dense/w_ffn_down') == xing4.LEAF_RTOL \
        == xing4.leaf_limit('layers/ws_down/0') \
        == xing4.leaf_limit('layers/w_qa/0')
    assert want[1] == pytest.approx(line['reference_global_grad_norm'] * (
        1 + harness.GRAD_NORM_RTOL * line['worst_in_limits']), rel=1e-9)
    assert norm_ok is not fails
    if not fails:
        assert loss_ok and line['worst_difference'] < 1e-3
        # what nothing reaches reads as nothing, not as a ratio of roundings
        assert all(leaves['dense/hc_attn/' + name] < 1e-3
                   for name in xing4.NOTHING_AT_ENTRY)


def test_a_gradient_where_nothing_reaches_is_read(case, wanted):
    """A program that did have a gradient at the first connection's
    ``H_pre`` (its streams not copies at the entry) reads 1 there, on the
    next connection's scale (read and printed, held to nothing: the family
    module says why), and a selection bias with one counts as a thousand
    limits."""
    _, _, _, _, (_, got_grads) = case
    want = wanted
    first = dict(got_grads['dense']['hc_attn'])
    for name in ('phi_pre', 'b_pre', 'alpha_pre'):
        first[name] = want['dense']['hc_mlp'][name]
    got = dict(got_grads, dense=dict(got_grads['dense'], hc_attn=first))
    readings = xing4.connection_differences(got, want)
    assert readings['dense/hc_attn/b_pre'] == pytest.approx(1.0, rel=1e-3)
    assert readings['dense/hc_attn/b_pre'] \
        == readings['dense/hc_attn/phi_pre'] \
        == readings['dense/hc_attn/alpha_pre']
    assert readings['dense/hc_attn/b_res'] < 1e-3
    got = dict(got_grads, layers=dict(
        got_grads['layers'], b_select=jnp.full((2, 8), 1e-9)))
    norm = xing4.held_to_every_leaf(1.0, got, want, 1)
    assert norm == pytest.approx(1 + harness.GRAD_NORM_RTOL * 1e3)


@pytest.mark.parametrize('fault,reads', [
    ('sound', 0.0), ('missing', 1.0), ('wrong_sign', 2.0), ('halved', 0.5),
    ('noise_at_right_angles', 0.0)])
def test_the_reads_and_the_mixes_are_held_along_the_reference(case, wanted,
                                                              fault, reads):
    """A connection's ``pre`` and ``res`` leaves are held by the component
    of the program's gradient ALONG the reference's (the family module
    says why): a missing gradient reads 1, a wrong sign 2, half the size
    0.5, and noise at right angles to the reference, as large as the
    reference itself, next to nothing where the L2 reading has 1."""
    _, _, _, _, (_, got_grads) = case
    want = wanted
    ref = want['layers']['hc_mlp']
    mlp = dict(got_grads['layers']['hc_mlp'])
    for name in ('phi_res', 'b_res', 'alpha_res'):
        a = np.asarray(ref[name], np.float64)
        if fault == 'noise_at_right_angles' and name == 'phi_res':
            noise = np.random.default_rng(0).normal(size=a.shape)
            flat, r = noise.reshape(len(a), -1), a.reshape(len(a), -1)
            flat -= np.sum(flat * r, 1, keepdims=True) \
                / np.sum(r * r, 1, keepdims=True) * r
            flat *= np.linalg.norm(r, axis=1, keepdims=True) \
                / np.linalg.norm(flat, axis=1, keepdims=True)
            a = a + flat.reshape(a.shape)
        mlp[name] = a * {'missing': 0.0, 'wrong_sign': -1.0,
                         'halved': 0.5}.get(fault, 1.0)
    got = dict(got_grads, layers=dict(got_grads['layers'], hc_mlp=mlp))
    l2 = {}
    readings = xing4.connection_differences(got, want, l2=l2)
    for layer in (0, 1):
        held = [readings['layers/hc_mlp/%s_res/%d' % (leaf, layer)]
                for leaf in ('phi', 'b', 'alpha')]
        assert held[0] == held[1] == held[2] == pytest.approx(reads,
                                                              abs=1e-6)
        apart = l2['layers/hc_mlp/phi_res/%d' % layer]
        assert apart == pytest.approx(
            1.0 if fault == 'noise_at_right_angles' else reads, abs=0.02)
    # the other kind of the same connection, and the write-back, as they were
    assert readings['layers/hc_mlp/phi_pre/0'] < 1e-3
    assert readings['layers/hc_mlp/alpha_post/1'] < 1e-3
    assert set(l2) == {'%s/phi%s%s' % (c, k, i) for c, idx in (
        ('dense/hc_mlp', ['']), ('layers/hc_attn', ['/0', '/1']),
        ('layers/hc_mlp', ['/0', '/1'])) for k in xing4.HC_MIX for i in idx}


def test_the_write_back_is_one_reading_a_connection(case, wanted):
    """``phi_post``, ``b_post`` and the gate of a connection are held by ONE
    L2 reading over the three together (the family module says why: the
    bias's and the gate's own are remainders that swing with the seed). A
    bias moved by a twentieth of the connection's scale reads a twentieth
    on all three and nothing anywhere else; what the bias reads alone, many
    times its own size, is kept beside, held to nothing. Every reading is a
    Python float."""
    _, _, _, _, (_, got_grads) = case
    apart = {}
    sound = xing4.connection_differences(got_grads, wanted)
    readings = xing4.connection_differences(pushed(got_grads), wanted,
                                            post_apart=apart)
    assert all(type(x) is float for x in readings.values())
    assert all(type(x) is float for x in apart.values())
    ref = wanted['layers']['hc_mlp']
    scale = np.sqrt(sum(np.sum(np.square(np.asarray(ref[leaf][1], np.float64)))
                        for leaf in ('phi_post', 'b_post', 'alpha_post')))
    moved = 0.05 * np.linalg.norm(np.asarray(ref['phi_post'][1], np.float64))
    three = [readings['layers/hc_mlp/%s/1' % leaf]
             for leaf in ('phi_post', 'b_post', 'alpha_post')]
    assert three[0] == three[1] == three[2] == pytest.approx(
        moved / scale, rel=1e-2)
    assert 0.045 < three[0] < 0.05
    for name, reading in readings.items():
        if not name.startswith('layers/hc_mlp/') or not name.endswith(
                ('_post/1',)):
            assert reading == sound[name] and reading < 1e-3, name
    assert set(apart) == {name for name in readings if name.split('/')[2]
                          in ('b_post', 'alpha_post')}
    # on the bias's own scale (the walk's size: 0.05 x sqrt(n C), n C 128)
    assert apart['layers/hc_mlp/b_post/1'] == pytest.approx(0.566, rel=0.01)
    assert apart['layers/hc_mlp/alpha_post/1'] < 1e-3
    assert apart['layers/hc_mlp/b_post/0'] < 1e-3


@pytest.mark.parametrize('worst', ['a_matrix', 'a_connection'])
def test_the_raised_norm_is_a_python_float_whichever_leaf_is_worst(
        case, wanted, capsys, worst):
    """``held_to_every_leaf`` hands ``harness.close`` a Python float, and
    the comparison a ``bool`` that ``json`` takes, when the worst leaf in
    units of its limit is a connection's as when it is a matrix's: until
    PR 50 the connections' readings were numpy scalars, and a run whose
    worst leaf was one of them died writing its last line (PERF.md section
    6, PR 49)."""
    _, _, _, _, (_, got_grads) = case
    got = pushed(got_grads) if worst == 'a_connection' else dict(
        got_grads, head=1.01 * got_grads['head'])
    norm = xing4.held_to_every_leaf(np.float64(3.0), got, wanted, 1)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    # (the write-back's three leaves carry one reading: any of them)
    assert line['worst'] == 'head' if worst == 'a_matrix' else re.fullmatch(
        r'layers/hc_mlp/\w+_post/1', line['worst'])
    assert type(norm) is float
    assert norm == pytest.approx(3.0 * (
        1 + harness.GRAD_NORM_RTOL * line['worst_in_limits']), rel=1e-12)
    if worst == 'a_connection':
        assert line['worst_in_limits'] == pytest.approx(
            0.05 / xing4.HC_LEAF_RTOL, rel=0.02)
        assert line['hc_post_apart']['layers/hc_mlp/b_post/1'] > 0.5
    ok = harness.close(np.float64(3.0), norm, harness.GRAD_NORM_RTOL)
    assert type(ok) is bool and ok
    json.dumps({'reference_grad_norm': ok, 'norm': norm})


@pytest.mark.parametrize('trace', [False, True], ids=['untraced', 'traced'])
def test_rehearsal_of_the_cell(tmp_path, trace, monkeypatch, capsys):
    """The run loop with the new family and engine at the tiny size on the
    CPU, under the real cell's name so that ``BENCHMARK.json``'s lists
    apply. In f32: a connection's coefficients are one number for all of a
    token's lanes, so bf16's rounding of them averages over TOKENS alone,
    and the probe's 64 tokens here leave the leaves 5-20% apart where the
    cell's 8,192 leave them inside the limits (PERF.md section 6); the
    bf16 step itself is driven below.

    The traced rehearsal is the cell's as PR 50 left it. Its file says
    ``min_steps``, so the traced ``fit`` has that many steps with the last
    ``trace_steps`` traced. And the probe's ``b_post`` gradient of an expert
    layer's MLP connection is pushed until a CONNECTION'S leaf is the worst
    in units of its limit, which the rehearsals never had and one run in
    six on the chip did: the run goes through to its last line and the line
    is JSON (on PR 48's tree it died there: ``Object of type bool is not
    JSON serializable``)."""
    cell = dict(name=CELL, config='tiny', traffic='tiny', chips=1,
                engine='trainer_leaves_parked', parallel={'dp': 1},
                trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    if trace:
        from benchmark.engines import trainer_leaves, trainer_leaves_parked
        cell['min_steps'] = 12
        probe = trainer_leaves_parked.Engine.loss_and_grad_norm

        def pushed_probe(self, state, batch):
            got = probe(self, state, batch)
            grads = trainer_leaves.PROBE['gradients']
            hc = dict(grads['blocks']['global']['hc_mlp'])
            bias, n = np.array(hc['bias']), config['hc_mult']
            bias[0, n:2 * n] += 0.05 / 2.0 * np.linalg.norm(
                np.asarray(hc['phi'])[0][:, n:2 * n])
            hc['bias'] = bias
            stack = dict(grads['blocks']['global'], hc_mlp=hc)
            trainer_leaves.PROBE['gradients'] = dict(
                grads, blocks=dict(grads['blocks'], **{'global': stack}))
            return got
        monkeypatch.setattr(trainer_leaves_parked.Engine,
                            'loss_and_grad_norm', pushed_probe)
    config = tiny_config('float32', num_hidden_layers=2)
    result, lines = harness.rehearse(
        cell, config, TRAFFIC, PEAKS, seed=2147483693,
        trace=trace, out_dir=str(tmp_path))
    report = json.loads(lines[-1])
    assert result['correct'] is True, report['checks']
    assert json.loads(json.dumps(result)) == result
    assert all(type(ok) is bool for ok in report['checks'].values())
    assert all(type(x) is float for x in report['reference'].values())
    leaves = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith('{"gradient_leaves"')][-1]
    if trace:
        assert re.fullmatch(r'layers/hc_mlp/\w+_post/0', leaves['worst'])
        assert 0.5 < leaves['worst_in_limits'] < 0.65
    assert result['attempted'] == report['steps'] == (
        12 if trace else harness.MIN_STEPS)
    assert result['device']['platform'] == 'cpu'
    bench = benchmark_json()
    if trace:
        # no device plane on the CPU: the readers of the trace find
        # nothing and say so without raising; the counters are the
        # program's and are read here as on the chip
        assert set(result['metrics']) == {
            'compile_s', 'compile_cache_miss', 'step_wall_ms', 'step_hbm_gb',
            'hc_moe_rows_here_pct', 'hc_moe_load_max_over_mean',
            'hc_res_col_sum_err'}
        assert 0 < result['metrics']['hc_moe_rows_here_pct']['value'] <= 100
        assert 1 <= result['metrics'][
            'hc_moe_load_max_over_mean']['value'] <= 4
        assert 0 <= result['metrics']['hc_res_col_sum_err']['value'] < 1e-2
    else:
        assert set(result['metrics']) == {m['name']
                                          for m in bench['end_to_end']}
