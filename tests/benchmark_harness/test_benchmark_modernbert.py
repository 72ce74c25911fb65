"""Family ``modernbert`` (PR 26): the program against the plain reference
at a tiny size that keeps ModernBERT's structure, three wrong
architectures against the same tolerance, the analytic FLOPs against a
hand count, and a rehearsal of the cell's run loop on the CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, benchmark_json

from benchmark import flash_kinds, harness
from benchmark.generators import zipf_lm
from benchmark.models import modernbert

TRAFFIC = dict(generator='zipf_lm', seq=64, global_batch=4,
               zipf_exponent=1.1)
PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
CELL = 'modernbert-large.s8192.c1'

# Both sides compute in f32 on the CPU, so what separates them is the
# order of their sums: the largest difference on any gradient leaf is
# 1.0e-6 of the leaf's largest element (loss: 8e-8 relative). The
# tolerance is 1e-4 of the leaf's largest element (loss: 1e-5), a
# hundred times that, and still under what a wrong architecture or a
# lower precision moves: the tanh GELU is out by 1.1e-3 on its worst
# leaf, the program in bf16 by 1.7e-2, one rotary base for both layer
# kinds by 0.11 and no window by 0.38.
LEAF_RTOL = 1e-4
LOSS_RTOL = 1e-5


def tiny_config(dtype='float32'):
    """7 layers = layer 0 + 2 periods of 3, 4 heads of 16, a window of 8
    keys each side, seq 64: every kind of layer, the lead layer's
    identity norm and the scan over periods are all there."""
    return dict(
        name='tiny-modernbert', family='modernbert', num_hidden_layers=7,
        hidden_size=64, num_attention_heads=4, intermediate_size=96,
        vocab_size=256, max_position_embeddings=64,
        global_attn_every_n_layers=3, local_attention=16,
        global_rope_theta=160000.0, local_rope_theta=10000.0,
        norm_eps=1e-5, norm_bias=False, attention_bias=False,
        mlp_bias=False, decoder_bias=True, hidden_activation='gelu',
        tied_embeddings=True, dtype=dtype, remat=True, scan_layers=True,
        loss_chunk=0, task='masked_lm', mask_token_id=3, mask_rate=0.3)


def seeded_params(model):
    """Seeded weights with every scale and bias moved off its initial 1
    or 0, so that no norm or bias is invisible to the comparison."""
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.ndim <= 2 and a.shape[-1] in (64, 256) and a.size <= 512
        else a, params)


def reference_grads(config, ref_params, probe, **switches):
    def loss(p):
        return jnp.mean(jnp.stack([
            modernbert.reference_loss(p, jnp.asarray(t), jnp.asarray(y),
                                      config, **switches)
            for t, y in zip(probe['tokens'], probe['targets'])]))
    return jax.jit(jax.value_and_grad(loss))(ref_params)


@pytest.fixture(scope='module')
def case():
    config = tiny_config()
    model = modernbert.build(config)
    params = seeded_params(model)
    probe = next(zipf_lm.batches(TRAFFIC, config, 0, batch=4, stream=1))
    got = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    # the name map is linear (a permutation and reshapes), so it carries
    # the program's gradient tree to the reference's names as well
    return config, model, params, probe, (
        got[0], modernbert.to_reference_params(got[1]))


def worst_leaf(got, want):
    """Largest error over the gradient leaves, each as a share of the
    leaf's own largest element."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    return max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_program_agrees_with_the_reference_on_every_gradient_leaf(case):
    config, _, params, probe, (got_loss, got_grads) = case
    want_loss, want_grads = reference_grads(
        config, modernbert.to_reference_params(params), probe)
    assert abs(float(got_loss) - float(want_loss)) \
        <= LOSS_RTOL * float(want_loss)
    assert worst_leaf(got_grads, want_grads) <= LEAF_RTOL
    # and through the harness's own entry point, one sequence at a time
    loss, norm = modernbert.reference_loss_and_grad_norm(
        config, modernbert.to_reference_params(params), probe)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert norm == pytest.approx(float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g)) for g in jax.tree.leaves(want_grads)))),
        rel=1e-5)


@pytest.mark.parametrize('broken', ['window', 'local_theta', 'exact_gelu'])
def test_a_wrong_architecture_misses_the_tolerance_severalfold(case,
                                                               broken):
    """No window, both layer kinds on the global rotary base, the tanh
    GELU: each is at least five times outside the tolerance."""
    config, _, params, probe, (_, got_grads) = case
    _, wrong = reference_grads(
        config, modernbert.to_reference_params(params), probe,
        **{broken: False})
    assert worst_leaf(got_grads, wrong) > 5 * LEAF_RTOL


def test_the_tolerance_fails_a_bf16_computation(case):
    config, _, params, probe, _ = case
    model = modernbert.build(tiny_config('bfloat16'))
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    want_loss, want_grads = reference_grads(
        config, modernbert.to_reference_params(params), probe)
    assert worst_leaf(modernbert.to_reference_params(grads),
                      want_grads) > 5 * LEAF_RTOL
    # the chip check's tolerances, which are for bf16, hold
    assert harness.close(float(loss), float(want_loss), harness.LOSS_RTOL)


def test_name_map_covers_every_parameter(case):
    _, _, params, _, _ = case
    ref = modernbert.to_reference_params(params)
    assert sum(a.size for a in jax.tree.leaves(ref)) == sum(
        a.size for a in jax.tree.leaves(params))
    # layers 1..6 come out in depth order: w w g w w g
    kinds = params['blocks']
    np.testing.assert_array_equal(
        np.asarray(ref['rest']['w_o']),
        np.stack([np.asarray(kinds[k]['attn']['out']['kernel'][i])
                  for k, i in (('window', 0), ('window', 1), ('global', 0),
                               ('window', 2), ('window', 3),
                               ('global', 1))]))
    assert ref['rest']['w_i'].shape == (6, 64, 192)
    assert 'ln_attn' not in ref['first'] and 'ln_attn' in ref['rest']


# By hand, ModernBERT-large: a layer is 4 x 1024^2 = 4,194,304 (attention)
# + 3 x 1024 x 2624 = 8,060,928 (gated MLP) = 12,255,232 parameters, 28
# of them 343,146,496, plus the head's dense 1,048,576: 344,195,072, so
# 688,390,144 FLOPs forward; the tied decoder is 2 x 1024 x 50368 =
# 103,153,664; QK^T and PV are 4 x seq x 1024 in each of the 10 global
# layers and 4 x min(seq, 129) x 1024 in each of the 18 window layers.
@pytest.mark.parametrize('seq,attention', [
    (8192, 10 * 4 * 8192 * 1024 + 18 * 4 * 129 * 1024),   # 345,055,232
    (64, 28 * 4 * 64 * 1024),                             # 7,340,032
])
def test_flops_per_token_equal_a_hand_count(seq, attention):
    with open(os.path.join(BENCH, 'configs', 'modernbert-large.json')) as f:
        config = json.load(f)
    by_hand = 3 * (688390144 + 103153664 + attention)
    assert modernbert.flops_per_token(config, seq) == by_hand
    assert by_hand in (3409797120, 2396651520)
    # every published size is in the file as published, nothing reduced
    assert config['reduced'] == []
    for key, value in config['published'].items():
        if key in config:
            assert config[key] == value, key


def test_flash_kind_costs_equal_a_hand_count():
    # q of [4, 16, 8192, 64] in bf16 is 67,108,864 bytes; one matmul over
    # the whole square is 2 x 4 x 16 x 8192 x 8192 x 64 = 549,755,813,888
    # FLOPs, over the 129-key band 2 x 4 x 16 x 8192 x 129 x 64
    shape = dict(batch=4, heads=16, seq=8192, head_dim=64, itemsize=2)
    assert flash_kinds.call_cost(keys=8192, backward=False, **shape) == (
        2 * 549755813888, 4 * 67108864)
    assert flash_kinds.call_cost(keys=8192, backward=True, **shape) == (
        5 * 549755813888, 8 * 67108864)
    assert flash_kinds.call_cost(keys=129, backward=True, **shape) == (
        5 * 8657043456, 8 * 67108864)
    config = dict(num_hidden_layers=28, global_attn_every_n_layers=3,
                  local_attention=128)
    assert flash_kinds.layers_of(config, 'global') == 10
    assert flash_kinds.layers_of(config, 'window') == 18
    assert flash_kinds.keys_seen(config, 8192, 'window') == 129
    assert flash_kinds.keys_seen(config, 64, 'window') == 64
    assert flash_kinds.layers_of({'num_hidden_layers': 24}, 'window') is None


def test_kernel_names_tell_the_kinds_apart():
    """On lines of the cell's step as compiled for the v5e (operands cut)."""
    line = ('%%%s = bf16[4,16,8192,64]{3,2,1,0:T(8,128)(2,1)} custom-call('
            '%%p), custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(step_fn)/%s/block/attention/%s/pallas_call"}')
    hlo = '\n'.join([
        line % ('flash_fwd.17', 'jvp(block)', 'flash_fwd'),
        'ROOT ' + line % ('flash_dq_band.20', 'transpose(jvp())/while/body/'
                          'closed_call/checkpoint', 'flash_dq_band'),
        line % ('flash_fwd_band.31', 'transpose(jvp())/while/body/closed_call'
                '/checkpoint/rematted_computation', 'flash_fwd_band'),
        line % ('closed_call.8', 'jvp()', 'not_a_flash_name'),
        '%fusion.1 = f32[8]{0} fusion(%p), metadata={op_name="x/flash_fwd"}',
    ])
    assert flash_kinds.kernel_names(hlo) == {
        '%flash_fwd.17': 'flash_fwd', '%flash_dq_band.20': 'flash_dq_band',
        '%flash_fwd_band.31': 'flash_fwd_band', '%closed_call.8': None}
    assert flash_kinds.kind_heads(hlo, 'global') == {'%flash_fwd.17'}
    assert flash_kinds.kind_heads(hlo, 'window') == {
        '%flash_dq_band.20', '%flash_fwd_band.31'}


@pytest.mark.parametrize('trace', [False, True], ids=['untraced', 'traced'])
def test_rehearsal_of_the_cell(tmp_path, trace):
    """The run loop with the new family at the tiny size on the CPU,
    under the real cell's name so that ``BENCHMARK.json``'s lists apply."""
    cell = dict(name=CELL, config='tiny', traffic='tiny', chips=1,
                engine='trainer', parallel={'dp': 1}, trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    result, lines = harness.rehearse(
        cell, tiny_config('bfloat16'), TRAFFIC, PEAKS, seed=2147483653,
        trace=trace, out_dir=str(tmp_path))
    report = json.loads(lines[-1])
    assert result['correct'] is True, report['checks']
    assert result['device']['platform'] == 'cpu'
    bench = benchmark_json()
    if trace:
        # no device plane on the CPU: the readers of the trace, the four
        # new ones among them, find nothing and say so without raising
        assert set(result['metrics']) == {
            'compile_s', 'compile_cache_miss', 'step_wall_ms', 'step_hbm_gb'}
        listed = harness.metrics_for(CELL, bench['per_layer'])
        assert {'flash_global_ms_per_step', 'flash_global_roofline_pct',
                'flash_window_ms_per_step',
                'flash_window_roofline_pct'} <= set(listed)
        assert 'flash_roofline_pct' not in listed
        assert any('flash_window_roofline_pct' in line for line in lines)
    else:
        assert set(result['metrics']) == {m['name']
                                          for m in bench['end_to_end']}
