"""Family ``nemotron_h`` (PR 41): the program against the plain reference
at a tiny size that keeps the model's structure (nine single-mixer
layers ``MEMEM*EME``: Mamba-2 layers through the scan's kernels in
interpret mode, attention over grouped kv heads with no positional
signal, relu2 experts with sigmoid scores, a selection bias that is not
zero, a scale and a shared expert; 3 of 16 experts a token, 4 held),
each deliberate fault against the same limits, the sixteen shares
against the uncut layer, the analytic FLOPs and the new kernels' costs
against hand counts, and a rehearsal of the cell's run loop on the
CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, benchmark_json

from benchmark import harness, mla_kinds, ssm_kinds
from benchmark.generators import zipf_lm
from benchmark.models import nemotron_h

TRAFFIC = dict(generator='zipf_lm', seq=128, global_batch=2,
               zipf_exponent=0.5)
PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
CELL = 'nemotron-3-nano-30b-a3b.s8192.c1'

# Both sides compute in f32 on the CPU: what separates them is the order
# of their sums (as test_benchmark_kanana2.py), and in the Mamba-2
# layers the chunked form against the recurrence.
LEAF_RTOL = 2e-4
LOSS_RTOL = 1e-5


def tiny_config(dtype='float32', **over):
    with open(os.path.join(BENCH, 'configs',
                           'nemotron-3-nano-30b-a3b.json')) as f:
        config = json.load(f)
    config.update(
        name='tiny-nemotron-h', hidden_size=64, mamba_num_heads=4,
        n_groups=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=40, n_routed_experts=16,
        num_experts_per_tok=3, num_experts_held=4, vocab_size=64,
        max_position_embeddings=256, embed_init_scale=1.0, dtype=dtype)
    config.update(over)
    return config


def seeded_params(model, bias=0.3):
    """Seeded weights with every norm scale and ``D`` moved off their
    initial 1 and a selection bias that is not zero, so that selecting
    by ``s + b`` and weighing by ``s`` is told from doing both by one of
    them."""
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 256))

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if 'scale' in name or name.endswith("['d']"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if 'select_bias' in name:
            return bias * jax.random.normal(next(keys), a.shape)
        return a
    return jax.tree_util.tree_map_with_path(moved, params)


def reference_grads(config, ref_params, probe, **switches):
    def loss(p):
        return jnp.mean(jnp.stack([
            nemotron_h.reference_loss(p, jnp.asarray(t), jnp.asarray(y),
                                      config, **switches)
            for t, y in zip(probe['tokens'], probe['targets'])]))
    return jax.jit(jax.value_and_grad(loss))(ref_params)


@pytest.fixture(scope='module')
def case():
    config = tiny_config()
    model = nemotron_h.build(config)
    params = seeded_params(model)
    probe = next(zipf_lm.batches(TRAFFIC, config, 0, batch=2, stream=1))
    got = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    return config, model, params, probe, (
        got[0], nemotron_h.to_reference_params(got[1]))


def worst_leaf(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    return max(float(jnp.max(jnp.abs(a - b))
                     / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_program_agrees_with_the_reference_on_every_gradient_leaf(case):
    config, model, params, probe, (got_loss, got_grads) = case
    # nine layers, each one mixer, all unrolled under their own names
    assert model.cfg.mixers == 'MEMEM*EME'
    assert (model._lead, model._period, model._periods) == (9, (), 0)
    assert 'blocks' not in params and 'pos_embed' not in params
    want_loss, want_grads = reference_grads(
        config, nemotron_h.to_reference_params(params), probe)
    assert abs(float(got_loss) - float(want_loss)) \
        <= LOSS_RTOL * float(want_loss)
    assert worst_leaf(got_grads, want_grads) <= LEAF_RTOL
    # every leaf but the selection biases' has a gradient that is not
    # nothing; theirs is nothing on both sides
    for side in (got_grads, want_grads):
        for name, layer in side.items():
            if name.startswith('layer_') and 'b_select' in layer:
                layer = dict(layer)
                assert not np.any(np.asarray(layer.pop('b_select')))
            assert all(float(jnp.max(jnp.abs(g))) > 0
                       for g in jax.tree.leaves(layer)), name


def test_the_mixer_alone_agrees_with_the_reference_layer():
    """``Mamba2Mixer`` (the scan's kernels in interpret mode, two
    chunks) against ``reference_mamba_layer``, output and every
    gradient leaf."""
    from autodist_tpu.models.ssm import Mamba2Mixer
    config = tiny_config()
    mixer = Mamba2Mixer(64, 4, 64, 2, 128, dtype=jnp.float32)
    params = mixer.init(jax.random.PRNGKey(0))
    params['d'] = params['d'] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), (4,))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 64))
    dy = jax.random.normal(jax.random.PRNGKey(3), (256, 64))
    names = {'in': 'w_in', 'conv': 'w_conv', 'conv_bias': 'b_conv',
             'dt_bias': 'dt_bias', 'a_log': 'a_log', 'd': 'd',
             'norm': 'ln_gate', 'out': 'w_out'}

    def as_reference(p):
        return {names[k]: (v['kernel'] if 'kernel' in v else v['scale'])
                if isinstance(v, dict) else v for k, v in p.items()}
    got = jax.value_and_grad(
        lambda p: jnp.sum(mixer.apply(p, x)[0] * dy))(params)
    with jax.default_matmul_precision('highest'):
        want = jax.value_and_grad(lambda w: jnp.sum(
            nemotron_h.reference_mamba_layer(w, x[0], config) * dy))(
                as_reference(params))
    assert abs(float(got[0]) - float(want[0])) <= 1e-4 * abs(float(want[0]))
    assert worst_leaf(as_reference(got[1]), want[1]) <= LEAF_RTOL
    # the draw: softplus(dt_bias) log-uniform in [1e-3, 1e-1], A in
    # -[1, 16], D = 1
    drawn = mixer.init(jax.random.PRNGKey(5))
    step = np.asarray(jax.nn.softplus(drawn['dt_bias']))
    assert np.all((step >= 1e-3 * 0.999) & (step <= 1e-1 * 1.001))
    assert np.all((np.exp(drawn['a_log']) >= 1) & (np.exp(drawn['a_log'])
                                                   <= 16))
    assert np.all(np.asarray(drawn['d']) == 1)


FAULTS = {
    'no_conv_bias': dict(conv_bias=False),
    'd_left_out': dict(skip=False),
    'gate_after_the_norm': dict(gate_inside=False),
    'rotary_left_on': dict(rotary=True),
    'gated_experts': dict(gated=True),
    'selected_without_b': dict(select_bias=False),
    'no_shared_expert': dict(shared=False),
    'an_expert_dropped': dict(drop_expert=1),
}


@pytest.mark.parametrize('broken', sorted(FAULTS) + ['bf16_products'])
def test_a_wrong_reference_misses_the_tolerance_severalfold(case, broken):
    config, _, params, probe, (_, got_grads) = case
    switches = FAULTS.get(broken) or dict(matmul_dtype=jnp.bfloat16)
    _, wrong = reference_grads(
        config, nemotron_h.to_reference_params(params), probe, **switches)
    assert worst_leaf(got_grads, wrong) > 5 * LEAF_RTOL


def test_name_map_covers_every_parameter(case):
    _, _, params, _, _ = case
    ref = nemotron_h.to_reference_params(params)
    assert sum(a.size for a in jax.tree.leaves(ref)) == sum(
        a.size for a in jax.tree.leaves(params))
    assert ref['layer_1']['w_up'] is params['block_001']['mixer']['up']
    assert ref['layer_1']['w_up'].shape == (4, 64, 24)      # no gate
    assert ref['layer_1']['ws_up'].shape == (64, 40)
    assert ref['layer_0']['w_in'].shape == (64, 256 + 256 + 2 * 2 * 128 + 4)
    assert ref['layer_0']['w_conv'].shape == (4, 256 + 2 * 2 * 128)
    assert [ref['layer_5'][k].shape for k in ('w_q', 'w_k', 'w_v', 'w_o')] \
        == [(64, 64), (64, 32), (64, 32), (64, 64)]
    assert sorted(k for k in ref if k.startswith('layer_')) == [
        'layer_%d' % i for i in range(9)]


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """What ties the share to the model: the routed parts that the
    shares of an expert layer give (each chip its ``num_experts_held`` of
    the 16: here 16 shares of one) plus the shared expert counted ONCE
    are what the uncut reference gives for the whole layer, in the
    program and in the reference alike."""
    from autodist_tpu.models.core import relu2
    from autodist_tpu.models.moe import MoeMlp
    config = tiny_config(num_experts_held=16)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 32, 64), jnp.float32)
    whole = MoeMlp(64, 24, 16, top_k=3, act=relu2, gated=False,
                   scoring='sigmoid', select_bias=True, scale=2.5, shared=40)
    params = whole.init(jax.random.PRNGKey(0))
    params['select_bias'] = 0.3 * jax.random.normal(jax.random.PRNGKey(1),
                                                    (16,))
    w = {'w_router': params['router']['kernel'],
         'b_select': params['select_bias'], 'w_up': params['up'],
         'w_down': params['down'],
         'ws_up': params['shared']['up']['kernel'],
         'ws_down': params['shared']['down']['kernel']}
    want = _reference_moe(config, w, x[0])
    got_whole, _, _ = whole.apply(params, x)
    np.testing.assert_allclose(got_whole[0], want, rtol=2e-5, atol=2e-6)
    # each share's routed part (one expert a share; `first` is traced, so
    # the sixteen are one compiled program), the shared expert ONCE
    share = MoeMlp(64, 24, 16, top_k=3, held=(0, 1), act=relu2, gated=False,
                   scoring='sigmoid', select_bias=True, scale=2.5)
    routed = jax.jit(lambda up, down, first: share._held_part(
        x, params['router'], up, down, first, params['select_bias'])[0])
    total = sum(routed(params['up'][e:e + 1], params['down'][e:e + 1], e)
                for e in range(16))
    total = total + whole.shared.apply(params['shared'], x)
    np.testing.assert_allclose(total[0], want, rtol=2e-5, atol=2e-6)

    # and the reference's own shares (a share's experts first among the
    # router's outputs), the shared expert counted once
    def share_of(first, **switches):
        moved = {k: np.roll(w[k], -first, axis)
                 for k, axis in (('w_router', 1), ('b_select', 0))}
        return _reference_moe(
            dict(config, num_experts_held=4),
            dict(w, w_up=w['w_up'][first:first + 4],
                 w_down=w['w_down'][first:first + 4], **moved),
            x[0], **switches)
    parts = sum(share_of(first, shared=False) for first in range(0, 16, 4))
    shared_once = share_of(0) - share_of(0, shared=False)
    np.testing.assert_allclose(parts + shared_once, want, rtol=2e-5,
                               atol=2e-6)


def _reference_moe(config, w, x, **switches):
    with jax.default_matmul_precision('highest'):
        return nemotron_h.reference_expert_layer(
            jax.tree.map(jnp.asarray, w), x, config, **switches)


# By hand, this chip's share at seq 8192 (forward, a token, multiply-adds):
# a Mamba-2 layer's projections 2688 x 10304 + 4096 x 2688 = 38,707,200,
# its conv 4 x 6144 = 24,576, its scan at chunks of 128: C B^T 8 x 64 x
# 128 = 65,536, the masked product 64 x 64 x 64 = 262,144, the chunk's
# state and the entering state's part 2 x 64 x 64 x 128 = 1,048,576, the
# carry 64 x 64 x 128 / 128 = 4,096: 1,380,352; a layer 40,112,128.
# Attention: 2688 x 4608 + 4096 x 2688 = 23,396,352 and QK^T and PV at
# 128 over 4096 keys, 4096 x 32 x 256 = 33,554,432. An expert layer:
# router 344,064, shared 2 x 2688 x 3712 = 19,955,712, held experts at
# the expected 6 x 8 / 128 = 0.375 pairs x 2 x 2688 x 1856 = 3,741,696:
# 24,041,472. The head 2688 x 16384 = 44,040,192.
def test_flops_per_token_equal_a_hand_count():
    with open(os.path.join(BENCH, 'configs',
                           'nemotron-3-nano-30b-a3b.json')) as f:
        config = json.load(f)
    macs = 4 * 40112128 + (23396352 + 33554432) + 4 * 24041472 + 44040192
    assert nemotron_h.flops_per_token(config, 8192) == 3 * 2 * macs \
        == 2145632256
    assert nemotron_h.scan_flops_per_token(config) == 2 * 1380352
    by_kind = nemotron_h.layer_flops_per_token(config, 8192)
    assert by_kind == {'M': 2 * 40112128, '*': 2 * 56950784,
                       'E': 2 * 24041472}
    # 44.9% of the forward in the four state-space layers
    assert round(100 * 4 * by_kind['M'] / (2 * macs), 1) == 44.9
    assert nemotron_h.flops_per_token(config, 128) == 3 * 2 * (
        macs - 33554432 + 64 * 32 * 256)
    # every published number is in the file as published but the cut
    assert sorted(config['reduced']) == [
        'num_experts_held', 'num_hidden_layers', 'vocab_size']
    for key, value in config['published'].items():
        if key not in config['reduced'] + ['hybrid_override_pattern']:
            assert config[key] == value, key
    assert (config['published']['num_hidden_layers'],
            config['published']['vocab_size']) == (52, 131072)
    assert config['published']['hybrid_override_pattern'].startswith(
        config['hybrid_override_pattern'])
    assert config['hybrid_override_pattern'] == 'MEMEM*EME' \
        == nemotron_h.pattern(config)
    assert config['num_experts_held'] * 16 == config['n_routed_experts']
    assert config['vocab_size'] * 8 == config['published']['vocab_size']
    assert (config['hidden_size'], config['mamba_num_heads'],
            config['mamba_head_dim'], config['n_groups'],
            config['ssm_state_size'], config['conv_kernel'],
            config['chunk_size']) == (2688, 64, 64, 8, 128, 4, 128)
    # 667.0M parameters here, 10.67 GB at 16 bytes
    shapes = jax.eval_shape(nemotron_h.build(config).init,
                            jax.random.PRNGKey(0))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == 666963456 and round(count * 16 / 1e9, 2) == 10.67
    by_layer = {k: sum(a.size for a in jax.tree.leaves(v))
                for k, v in shapes.items()}
    assert (by_layer['block_000'], by_layer['block_001'],
            by_layer['block_005']) == (38744896, 100125440, 23399040)


def test_new_kernel_costs_equal_a_hand_count():
    # a call over 2 x 8192 tokens: multiply-adds a token as above, the
    # backward with C B^T three times, the masked products twice and
    # four state-sized products; bytes in bf16: x and y 4096 wide, B and
    # C 1024 each, dt 64
    shape = dict(batch=2, seq=8192, heads=64, head_dim=64, groups=8,
                 state=128, chunk=128)
    assert ssm_kinds.call_cost('ssd_fwd', **shape) == (
        2 * 16384 * 1380352, 2 * 16384 * (2 * 4096 + 2048 + 64))
    assert ssm_kinds.call_cost('ssd_bwd', **shape) == (
        2 * 16384 * (3 * 65536 + 2 * 262144 + 4 * 524288 + 4096),
        2 * 16384 * (3 * 4096 + 2 * 2048 + 2 * 64))
    with open(os.path.join(BENCH, 'configs',
                           'nemotron-3-nano-30b-a3b.json')) as f:
        config = json.load(f)
    assert ssm_kinds.dims(config) == (64, 64, 8, 128, 128)
    assert ssm_kinds.ssm_layers(config) == 4
    assert ssm_kinds.dims({'num_hidden_layers': 2}) is None
    # the forward's count is the family's (what mfu_pct counts)
    assert ssm_kinds.call_cost('ssd_fwd', **shape)[0] == 16384 \
        * nemotron_h.scan_flops_per_token(config)


def test_kernels_and_scopes_are_read_by_name():
    line = ('%%%s = f32[8]{0} %s(%%p), metadata={op_name="jit(step_fn)/'
            '%s"}')
    call = 'custom-call'
    kernel = ', custom_call_target="tpu_custom_call"'
    hlo = '\n'.join([
        line % ('ssd_fwd.1', call, 'jvp()/block/ssm/ssd_fwd/pallas_call')
        + kernel,
        line % ('ssd_fwd.2', call, 'transpose(jvp())/checkpoint/'
                'rematted_computation/block/ssm/ssd_fwd/pallas_call')
        + kernel,
        'ROOT ' + line % ('ssd_bwd.3', call, 'transpose(jvp())/block/ssm/'
                          'ssd_bwd/pallas_call') + kernel,
        line % ('flash_fwd.4', call, 'jvp()/block/attention/flash_fwd/'
                'pallas_call') + kernel,
        line % ('fusion.5', 'fusion', 'jvp()/block/ssm/ssm_mixer/'
                'dot_general'),
        line % ('fusion.6', 'fusion', 'jvp()/block/ssm/cumsum'),
        line % ('fusion.7', 'fusion', 'jvp()/block/mlp/moe_shared/mul'),
        line % ('fusion.8', 'fusion', 'jvp()/block/mlp/moe_route/top_k'),
    ])
    assert mla_kinds.kernel_heads(hlo, 'ssd_fwd') == {'%ssd_fwd.1',
                                                    '%ssd_fwd.2'}
    assert mla_kinds.kernel_heads(hlo, 'ssd_bwd') == {'%ssd_bwd.3'}
    assert mla_kinds._named_heads(hlo, ('ssm',), False) == {
        '%ssd_fwd.1', '%ssd_fwd.2', '%ssd_bwd.3', '%fusion.5', '%fusion.6'}
    assert mla_kinds._named_heads(hlo, ('ssm_mixer',), False) == {
        '%fusion.5'}
    assert mla_kinds._named_heads(
        hlo, mla_kinds.ROUTED_SCOPES + ('moe_shared',), False) == {
            '%fusion.7', '%fusion.8'}
    # a program without the names: nothing to read, and no error
    said = []
    run = {'hlo': hlo.replace('ssd_', 'other_').replace('ssm', 'block'),
           'say': said.append, 'config': {'num_hidden_layers': 2},
           'traffic': {}}

    class NoTrace:
        ops, steps = {0: []}, 1
    assert ssm_kinds.kernels_ms(NoTrace, run) is None
    assert ssm_kinds.roofline_pct(NoTrace, run, 'ssd_bwd') is None
    assert mla_kinds.scopes_ms(NoTrace, run, 'ssm') is None
    assert said and 'nothing to read' in said[0]


