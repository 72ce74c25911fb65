"""Family ``mellum2`` (PR 33): the program against the plain reference at
a tiny size that keeps Mellum2's structure (a period of three causal
window layers and a full causal YaRN layer, grouped kv heads, top-2 of 8
experts of which 4 are held), wrong architectures and a lower precision
against the same tolerance, the analytic FLOPs and the new kernels'
costs against hand counts, and a rehearsal of the cell's run loop on the
CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, benchmark_json

from benchmark import harness, moe_kinds
from benchmark.generators import zipf_lm
from benchmark.models import mellum2

TRAFFIC = dict(generator='zipf_lm', seq=32, global_batch=4,
               zipf_exponent=0.5)
PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
CELL = 'mellum2-12b-a2.5b.s8192.c1'

# Both sides compute in f32 on the CPU, so what separates them is the
# order of their sums: the largest difference on any gradient leaf is
# about 1e-6 of the leaf's largest element. The tolerance is 1e-4 of it
# (loss: 1e-5), far under what a wrong architecture moves (below).
LEAF_RTOL = 1e-4
LOSS_RTOL = 1e-5


def tiny_config(dtype='float32', **over):
    config = dict(
        name='tiny-mellum2', family='mellum2', num_hidden_layers=4,
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, vocab_size=64, max_position_embeddings=64,
        layer_types=['sliding_attention'] * 3 + ['full_attention'],
        mlp_layer_types=['sparse'] * 4, sliding_window=8,
        rope_parameters={
            'full_attention': dict(
                rope_type='yarn', rope_theta=500000, factor=16,
                original_max_position_embeddings=16, beta_fast=32,
                beta_slow=1, attention_factor=1.2772588722239782),
            'sliding_attention': dict(rope_type='default',
                                      rope_theta=500000)},
        hidden_act='silu', attention_bias=False, tie_word_embeddings=False,
        norm_topk_prob=True, rms_norm_eps=1e-6, num_experts=8,
        num_experts_held=4, num_experts_per_tok=2, moe_intermediate_size=16,
        moe_aux_coef=0.0, embed_init_scale=1.0, dtype=dtype, remat=True,
        scan_layers=True, loss_chunk=0, task='causal_lm')
    config.update(over)
    return config


def seeded_params(model):
    """Seeded weights with every norm scale moved off its initial 1."""
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.shape[-1] == 32 and a.ndim <= 2 and a.size <= 128 else a,
        params)


def reference_grads(config, ref_params, probe, **switches):
    def loss(p):
        return jnp.mean(jnp.stack([
            mellum2.reference_loss(p, jnp.asarray(t), jnp.asarray(y),
                                   config, **switches)
            for t, y in zip(probe['tokens'], probe['targets'])]))
    return jax.jit(jax.value_and_grad(loss))(ref_params)


@pytest.fixture(scope='module')
def case():
    config = tiny_config()
    model = mellum2.build(config)
    params = seeded_params(model)
    probe = next(zipf_lm.batches(TRAFFIC, config, 0, batch=4, stream=1))
    got = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    return config, model, params, probe, (
        got[0], mellum2.to_reference_params(got[1]))


def worst_leaf(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    return max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_program_agrees_with_the_reference_on_every_gradient_leaf(case):
    config, model, params, probe, (got_loss, got_grads) = case
    assert model._period == ('window', 'window', 'window', 'global')
    want_loss, want_grads = reference_grads(
        config, mellum2.to_reference_params(params), probe)
    assert abs(float(got_loss) - float(want_loss)) \
        <= LOSS_RTOL * float(want_loss)
    assert worst_leaf(got_grads, want_grads) <= LEAF_RTOL
    # every leaf has a gradient that is not nothing (the held experts'
    # among them), and through the harness's own entry point
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(want_grads))
    loss, norm = mellum2.reference_loss_and_grad_norm(
        config, mellum2.to_reference_params(params), probe)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert norm == pytest.approx(float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g)) for g in jax.tree.leaves(want_grads)))),
        rel=1e-5)


@pytest.mark.parametrize('broken', [
    dict(window=False), dict(yarn=False), dict(drop_expert_rows=1),
    dict(experts_dtype=jnp.bfloat16)],
    ids=['no_window', 'no_yarn', 'a_dropped_row', 'bf16_experts'])
def test_a_wrong_reference_misses_the_tolerance_severalfold(case, broken):
    """No window, the window layers' frequencies in the full layer, one
    position left out of the experts, the experts' products in bf16:
    each is at least five times outside the tolerance."""
    config, _, params, probe, (_, got_grads) = case
    _, wrong = reference_grads(
        config, mellum2.to_reference_params(params), probe, **broken)
    assert worst_leaf(got_grads, wrong) > 5 * LEAF_RTOL


def through_the_harness(config, params, probe, **switches):
    """``(checks pass, the line the family printed)`` of the cell's own
    comparison: ``trainer_leaves``'s probe against the family's reference
    under ``harness.close`` at the harness's limits."""
    from benchmark.engines import trainer_leaves
    engine = trainer_leaves.Engine(mellum2.build(config), {'dp': 1},
                                   jax.devices()[:1])
    state = engine.trainer.init(None, params=params)
    got = engine.loss_and_grad_norm(state, probe)
    assert set(trainer_leaves.PROBE) == {'gradients'}
    want = mellum2.reference_loss_and_grad_norm(
        config, mellum2.to_reference_params(params), probe, **switches)
    assert 'gradients' not in trainer_leaves.PROBE     # taken, not left
    return (harness.close(got[0], want[0], harness.LOSS_RTOL),
            harness.close(got[1], want[1], harness.GRAD_NORM_RTOL)), got, want


@pytest.mark.parametrize('broken,fails', [
    ({}, False), (dict(window=False), True), (dict(yarn=False), True),
    (dict(drop_expert=1), True), (dict(drop_expert_rows=4), True),
    (dict(experts_dtype=jnp.float8_e4m3fn), True),
    (dict(matmul_dtype=jnp.float8_e4m3fn), True)],
    ids=['sound', 'no_window', 'no_yarn', 'an_expert_dropped',
         'rows_dropped', 'fp8_experts', 'fp8_products'])
def test_the_cells_own_comparison_holds_every_leaf(case, capsys, broken,
                                                   fails):
    """Through the engine and the family as ``harness.py`` calls them:
    the sound reference passes, and a reference that is wrong in one
    layer's mechanism fails ``reference_grad_norm`` though its GLOBAL
    norm may agree: the norm the family returns is raised by the worst
    leaf's difference."""
    config, _, params, probe, _ = case
    probe = {k: v[:2] for k, v in probe.items()}
    (loss_ok, norm_ok), got, want = through_the_harness(
        config, params, probe, **broken)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line['limits'] == {'leaf': mellum2.LEAF_RTOL,
                              'routed_leaf': mellum2.ROUTED_LEAF_RTOL}
    leaves = line['gradient_leaves']
    assert len(leaves) == 3 + 7 * 4                     # a layer at a time
    assert line['worst_in_limits'] == pytest.approx(max(
        d / mellum2.leaf_limit(name) for name, d in leaves.items()))
    assert want[1] == pytest.approx(line['reference_global_grad_norm'] * (
        1 + harness.GRAD_NORM_RTOL * line['worst_in_limits']), rel=1e-9)
    assert norm_ok is not fails
    if not fails:
        assert loss_ok and line['worst_difference'] < 1e-4


def test_the_reference_alone_returns_its_global_norm(case):
    """Without a probe left by ``trainer_leaves`` (the plain ``trainer``
    engine, the tests above) the family returns the global norm."""
    from benchmark.engines import trainer_leaves
    config, _, params, probe, _ = case
    trainer_leaves.PROBE.clear()
    _, want_grads = reference_grads(
        config, mellum2.to_reference_params(params), probe)
    _, norm = mellum2.reference_loss_and_grad_norm(
        config, mellum2.to_reference_params(params), probe)
    assert norm == pytest.approx(float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g)) for g in jax.tree.leaves(want_grads)))),
        rel=1e-5)


def test_the_probe_reads_the_gradient_of_a_large_parameter_back():
    """Rows at N(0, 8^2), as the cell draws them: ``old - new`` after a
    step of SGD(1.0) would lose a gradient of 1e-6 an element in the
    rounding of a parameter of size 8; ``trainer_leaves`` steps at 2^20
    and every leaf comes back to 1e-5 of ``jax.grad``'s."""
    from benchmark.engines import trainer_leaves
    config = tiny_config(embed_init_scale=8.0)
    model = mellum2.build(config)
    engine = trainer_leaves.Engine(model, {'dp': 1}, jax.devices()[:1])
    state = engine.init(3)
    probe = next(zipf_lm.batches(TRAFFIC, config, 3, batch=2, stream=1))
    loss, norm = engine.loss_and_grad_norm(state, probe)
    want_loss, want = jax.jit(jax.value_and_grad(model.loss))(
        state.params, probe)
    got = trainer_leaves.PROBE.pop('gradients')
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(
            jnp.linalg.norm(b))
    assert norm == pytest.approx(float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g)) for g in jax.tree.leaves(want)))), rel=1e-5)
    # at 1.0, and the chip's 1e-6 an element (a thousandth of this tiny
    # model's), the embedding's leaf is lost in its parameter's rounding
    table, small = state.params['embed']['table'], \
        1e-3 * want['embed']['table']
    lost = (table - (table - small)) - small
    assert float(jnp.linalg.norm(lost)) > 0.05 * float(jnp.linalg.norm(small))
    kept = (table - (table - small * 2.0 ** 20)) / 2.0 ** 20 - small
    assert float(jnp.linalg.norm(kept)) < 1e-4 * float(jnp.linalg.norm(small))


def test_the_chip_tolerances_hold_for_the_program_in_bf16(case):
    config, _, params, probe, _ = case
    model = mellum2.build(tiny_config('bfloat16'))
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    want_loss, want_grads = reference_grads(
        config, mellum2.to_reference_params(params), probe)
    assert worst_leaf(mellum2.to_reference_params(grads),
                      want_grads) > 5 * LEAF_RTOL
    assert harness.close(float(loss), float(want_loss), harness.LOSS_RTOL)


def test_name_map_covers_every_parameter_and_copies_none(case):
    _, _, params, _, _ = case
    ref = mellum2.to_reference_params(params)
    assert sum(a.size for a in jax.tree.leaves(ref)) == sum(
        a.size for a in jax.tree.leaves(params))
    assert ref['layers']['window']['w_gate_up'].shape == (3, 4, 32, 2, 16)
    assert ref['layers']['global']['w_qkv'].shape == (1, 32, 8 * 16)
    assert ref['head'] is params['lm_head']['kernel']


def test_yarn_frequencies_are_the_published_formula():
    """``rope_frequencies`` (the program) and ``rotary_inv_freq`` (the
    reference) against the formula worked by hand at Mellum2's sizes:
    ``dim(32) = 128 ln(8192 / 64 pi) / (2 ln 500000) = 18.08``, ``dim(1) =
    34.99``: pairs 0..18 keep their frequency, pairs 35..63 are divided
    by 16, a ramp between."""
    from autodist_tpu.models.attention import rope_frequencies
    with open(os.path.join(BENCH, 'configs', 'mellum2-12b-a2.5b.json')) as f:
        rope = json.load(f)['rope_parameters']['full_attention']
    inv_freq, factor = mellum2.rotary_inv_freq(rope, 128)
    plain = 500000.0 ** (-2.0 * np.arange(64) / 128)
    np.testing.assert_allclose(inv_freq[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, rtol=1e-12)
    ramp = (27 - 18) / (35 - 18)
    assert inv_freq[27] == pytest.approx(
        plain[27] / 16 * ramp + plain[27] * (1 - ramp), rel=1e-12)
    assert factor == 1.2772588722239782
    got, got_factor = rope_frequencies(500000.0, 128, rope)
    np.testing.assert_allclose(got, inv_freq, rtol=1e-6)
    assert got_factor == factor


# By hand, Mellum2 on this chip: a layer's attention is 2 x 2304 x 4096 +
# 2 x 2304 x 512 = 21,233,664 parameters, its router 2304 x 64 = 147,456,
# its held experts at the expected 8 x 16 / 64 = 2 pairs a token 2 x 3 x
# 2304 x 896 = 12,386,304: 33,767,424 a layer, four layers 135,069,696, so
# 270,139,392 FLOPs forward; the head 2 x 2304 x 24576 = 113,246,208; QK^T
# and PV 4 x keys x 4096 with keys 1024 in each of the three window layers
# and 8192 / 2 in the full one: 4 x 7168 x 4096 = 117,440,512.
def test_flops_per_token_equal_a_hand_count():
    with open(os.path.join(BENCH, 'configs', 'mellum2-12b-a2.5b.json')) as f:
        config = json.load(f)
    by_hand = 3 * (270139392 + 113246208 + 117440512)
    assert mellum2.flops_per_token(config, 8192) == by_hand == 1502478336
    # at 32 positions the window is the sequence: 3 x 32 + 16 keys
    assert mellum2.flops_per_token(config, 32) == 3 * (
        270139392 + 113246208 + 4 * 112 * 4096)
    # every published number is in the file as published but the three cut
    assert sorted(config['reduced']) == [
        'num_experts_held', 'num_hidden_layers', 'vocab_size']
    for key, value in config['published'].items():
        if key not in config['reduced']:
            assert config[key] == value, key
    assert (config['published']['num_hidden_layers'],
            config['published']['vocab_size']) == (28, 98304)
    assert config['num_experts_held'] * 4 == config['num_experts']
    assert config['vocab_size'] * 4 == config['published']['vocab_size']


def test_new_kernel_costs_equal_a_hand_count():
    # q of [4, 32, 8192, 128] in bf16 is 268,435,456 bytes, k of 4 heads
    # 33,554,432; one matmul over the causal half of the square is 2 x 4
    # x 32 x 8192 x 4096 x 128 = 1,099,511,627,776 FLOPs, over the band
    # of 1024 keys a quarter of that
    shape = dict(batch=4, heads=32, kv_heads=4, seq=8192, head_dim=128)
    assert moe_kinds.gqa_call_cost(keys=4096, backward=False, **shape) == (
        2 * 1099511627776, 2 * 268435456 + 2 * 33554432)
    assert moe_kinds.gqa_call_cost(keys=1024, backward=True, **shape) == (
        5 * 274877906944, 4 * 268435456 + 4 * 33554432)
    # a layer's experts over 65,536 live rows: 3 x 65536 x 2 x 3 x 2304 x
    # 896 FLOPs; 5 rows of 2304 bf16 each, 16 x 3 x 2304 x 896 weights read
    # three times in bf16 and written once in f32
    config = dict(hidden_size=2304, moe_intermediate_size=896,
                  num_experts_held=16)
    assert moe_kinds.experts_cost(config, 65536) == (
        2435246456832, 65536 * 5 * 2304 * 2 + 99090432 * 10)
    assert moe_kinds.gqa_layers(
        dict(num_hidden_layers=4, layer_types=['sliding_attention'] * 3
             + ['full_attention'] * 25), 'window') == 3
    assert moe_kinds.gqa_layers({'num_hidden_layers': 24}, 'global') is None


def test_scopes_of_the_expert_layer_are_read_by_name():
    line = ('%%%s = f32[8]{0} fusion(%%p), metadata={op_name="jit(step_fn)/'
            '%s"}')
    hlo = '\n'.join([
        line % ('fusion.1', 'jvp()/while/body/block/mlp/moe_route/top_k'),
        'ROOT ' + line % ('moe_gmm.3', 'transpose(jvp())/while/body/block/'
                          'mlp/while/body/moe_experts/moe_gmm/pallas_call'),
        line % ('scatter.9', 'jvp()/block/mlp/while/body/moe_dispatch/'
                'scatter-add'),
        line % ('fusion.7', 'jvp()/block/mlp/add'),
    ])
    assert moe_kinds.scope_heads(hlo, 'moe_route') == {'%fusion.1'}
    assert moe_kinds.scope_heads(hlo, 'moe_experts') == {'%moe_gmm.3'}
    assert moe_kinds.scope_heads(hlo, 'moe_dispatch') == {'%scatter.9'}


@pytest.mark.parametrize('trace', [False, True], ids=['untraced', 'traced'])
def test_rehearsal_of_the_cell(tmp_path, trace):
    """The run loop with the new family at the tiny size on the CPU,
    under the real cell's name so that ``BENCHMARK.json``'s lists apply."""
    cell = dict(name=CELL, config='tiny', traffic='tiny', chips=1,
                engine='trainer_leaves', parallel={'dp': 1}, trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    result, lines = harness.rehearse(
        cell, tiny_config('bfloat16'), TRAFFIC, PEAKS, seed=2147483653,
        trace=trace, out_dir=str(tmp_path))
    report = json.loads(lines[-1])
    assert result['correct'] is True, report['checks']
    assert result['device']['platform'] == 'cpu'
    bench = benchmark_json()
    if trace:
        # no device plane on the CPU: the readers of the trace find
        # nothing and say so without raising; the counters are the
        # program's and are read here as on the chip
        assert set(result['metrics']) == {
            'compile_s', 'compile_cache_miss', 'step_wall_ms', 'step_hbm_gb',
            'moe_rows_here_pct', 'moe_load_max_over_mean'}
        listed = harness.metrics_for(CELL, bench['per_layer'])
        assert {'moe_route_ms_per_step', 'moe_dispatch_ms_per_step',
                'moe_experts_ms_per_step', 'moe_experts_roofline_pct',
                'flash_gqa_causal_roofline_pct',
                'flash_gqa_band_roofline_pct', 'flash_gqa_causal_ms_per_step',
                'flash_gqa_band_ms_per_step'} <= set(listed)
        assert 'flash_roofline_pct' not in listed
        assert 0 < result['metrics']['moe_rows_here_pct']['value'] <= 100
        assert 1 <= result['metrics']['moe_load_max_over_mean']['value'] <= 4
    else:
        assert set(result['metrics']) == {m['name']
                                          for m in bench['end_to_end']}
