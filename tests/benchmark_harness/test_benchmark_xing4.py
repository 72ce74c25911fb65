"""Family ``xing4`` (PR 48): the program against the plain reference at a
tiny size that keeps the model's structure (four residual streams mixed
by hyper-connections whose coefficients move with the token, latent
attention with a q down-projection and YaRN, a leading dense layer, expert
layers with sigmoid scores, a selection bias that is not zero, a scale and
a shared expert; 2 of 8 experts a token, 4 held), each deliberate fault
against the same limits, the shares against the uncut layer, the analytic
FLOPs and the parameter count against hand counts and the readers of the
new names. The cell's own comparison through its engine and the rehearsal
of its run loop are in ``test_benchmark_xing4_cell.py`` (a file of their
own: tier-1 hands a file to one worker)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, benchmark_json

from benchmark import harness, hc_kinds
from benchmark.generators import zipf_lm
from benchmark.models import kanana2, xing4

TRAFFIC = dict(generator='zipf_lm', seq=32, global_batch=4,
               zipf_exponent=0.5)
PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
CELL = 'xing4.0-29b-a4b.s4096.c1'

# Both sides compute in f32 on the CPU: what separates them is the order
# of their sums, about 1e-6 of a leaf's largest element (as
# test_benchmark_kanana2.py); the twenty rounds of divisions carry a few
# times that to the connections' leaves.
LEAF_RTOL = 3e-4
LOSS_RTOL = 1e-5


def tiny_config(dtype='float32', **over):
    config = dict(
        name='tiny-xing4', family='xing4', num_hidden_layers=3,
        hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
        vocab_size=64, max_position_embeddings=64, attention_bias=False,
        first_k_dense_replace=2, num_dense_layers_run=1, hidden_act='silu',
        intermediate_size=48, kv_lora_rank=16, q_lora_rank=12,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
        rope_theta=10000,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=16, type='yarn'),
        moe_intermediate_size=16, moe_layer_freq=1, n_group=1, topk_group=1,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        norm_topk_prob=True, routed_scaling_factor=2, scoring_func='sigmoid',
        topk_method='noaux_tc', tie_word_embeddings=False,
        rms_norm_eps=1e-6, num_experts_held=4, moe_aux_coef=0.0,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
        num_nextn_predict_layers=1, hc_alpha_init=1.0,
        hc_bias_init_scale=0.5, hc_res_diag_init=2.0,
        embed_init_scale=1.0, dtype=dtype, remat=True, scan_layers=True,
        loss_chunk=0, task='causal_lm')
    config.update(over)
    return config


def seeded_params(model, bias=0.3):
    """The family's draw (gates 1, biases N(0, 0.5^2) round 0 and round 2
    on the stream mix's diagonal) with every norm scale moved off its
    initial 1 and a selection bias that is not zero."""
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if path[-1].key == 'scale' else a, params)
    mlp = params['blocks']['global']['mlp']
    mlp['select_bias'] = bias * jax.random.normal(
        jax.random.PRNGKey(2), mlp['select_bias'].shape)
    return params


def reference_grads(config, ref_params, probe, **switches):
    """Loss and gradient of the reference on ``probe``: the mean over its
    sequences (one traced body under ``vmap``: a loop over them unrolls the
    twenty rounds a sequence and compiles for a minute a case)."""
    def loss(p):
        return jnp.mean(jax.vmap(
            lambda t, y: xing4.reference_loss(p, t, y, config, **switches))(
                jnp.asarray(probe['tokens']), jnp.asarray(probe['targets'])))
    return jax.jit(jax.value_and_grad(loss))(ref_params)


@pytest.fixture(scope='module')
def case():
    config = tiny_config()
    model = xing4.build(config)
    params = seeded_params(model)
    probe = next(zipf_lm.batches(TRAFFIC, config, 0, batch=2, stream=1))
    got = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    return config, model, params, probe, (
        got[0], xing4.to_reference_params(got[1]))


def worst_leaf(got, want):
    """The largest of the leaves' ``max |got - want| / max |want|``; the
    first connection's six leaves that nothing reaches
    (``xing4.NOTHING_AT_ENTRY``) over the next connection's ``want``."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    after = want['dense']['hc_mlp']
    worst = 0.0
    for a, (path, b) in zip(jax.tree.leaves(got), flat):
        keys = [k.key for k in path]
        scale = b
        if keys[:2] == ['dense', 'hc_attn'] \
                and keys[2] in xing4.NOTHING_AT_ENTRY:
            scale = after[keys[2]]
        worst = max(worst, float(jnp.max(jnp.abs(a - b)) / jnp.maximum(
            jnp.max(jnp.abs(scale)), 1e-30)))
    return worst


def test_program_agrees_with_the_reference_on_every_gradient_leaf(case):
    config, model, params, probe, (got_loss, got_grads) = case
    # the dense layer leads, unrolled; the expert layers scan
    assert (model._lead, model._period, model._periods) == (
        1, ('global',), 2)
    want_loss, want_grads = reference_grads(
        config, xing4.to_reference_params(params), probe)
    assert abs(float(got_loss) - float(want_loss)) \
        <= LOSS_RTOL * float(want_loss)
    assert worst_leaf(got_grads, want_grads) <= LEAF_RTOL
    # nothing reaches the first connection's H_pre and H_res (the streams
    # are copies of one row there): 1e-4 of the next connection's, on
    # both sides; every other leaf but the selection bias's has a gradient
    for side in (got_grads, want_grads):
        first, after = side['dense']['hc_attn'], side['dense']['hc_mlp']
        for name in xing4.NOTHING_AT_ENTRY:
            assert float(jnp.max(jnp.abs(first[name]))) \
                < 1e-4 * float(jnp.max(jnp.abs(after[name]))), name
        layers = dict(side['layers'])
        assert not np.any(np.asarray(layers.pop('b_select')))
        assert all(float(jnp.max(jnp.abs(g))) > 0
                   for g in jax.tree.leaves(dict(side, layers=layers)))


FAULTS = {
    'one_sinkhorn_round': dict(sinkhorn_iters=1),
    'rows_then_columns': dict(row_first=True),
    'h_post_without_its_2': dict(post_factor=1.0),
    'h_pre_by_softmax': dict(pre='softmax'),
    'first_stream_alone_at_the_exit': dict(exit='first'),
    'q_without_its_norm': dict(q_norm=False),
    'scale_without_mscale_squared': dict(score_factor=False),
    'yarn_off': dict(yarn=False),
}


@pytest.mark.parametrize('broken', list(FAULTS.values()) + [
    dict(matmul_dtype=jnp.bfloat16)], ids=list(FAULTS) + ['bf16_products'])
def test_a_wrong_reference_misses_the_tolerance_severalfold(case, broken):
    config, _, params, probe, (_, got_grads) = case
    _, wrong = reference_grads(
        config, xing4.to_reference_params(params), probe, **broken)
    assert worst_leaf(got_grads, wrong) > 5 * LEAF_RTOL


def test_the_streams_averaged_at_the_exit_are_the_sum_under_the_norm(case):
    """ISSUE 48 lists "the streams averaged at the exit" among the wrong
    references; the final RMSNorm takes the factor ``1 / n`` out again
    (all but ``rms_norm_eps``'s part), so that reference is the sound one
    and no comparison can tell them apart. The exit's fault in the list
    above is the first stream read alone."""
    config, _, params, probe, (_, got_grads) = case
    _, same = reference_grads(
        config, xing4.to_reference_params(params), probe, exit='mean')
    assert worst_leaf(got_grads, same) <= LEAF_RTOL


def test_a_program_with_a_fault_misses_it_too(case):
    """The other way round: the PROGRAM with one round for the twenty
    against the sound reference."""
    config, _, params, probe, _ = case
    want = reference_grads(config, xing4.to_reference_params(params),
                           probe)[1]
    model = xing4.build(dict(config, hc_sinkhorn_iters=1))
    got = jax.jit(jax.grad(model.loss))(params, probe)
    assert worst_leaf(xing4.to_reference_params(got), want) > 5 * LEAF_RTOL


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What ties the share to the model: the routed parts that the shares
    of an expert layer give (each chip its ``num_experts_held`` of the 8:
    here 4 shares of 2) plus the shared expert counted ONCE are what the
    uncut reference gives for the whole layer, in the program and in the
    reference alike."""
    from autodist_tpu.models.moe import MoeMlp
    config = tiny_config(num_experts_held=8)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 32, 32), jnp.float32)
    whole = MoeMlp(32, 16, 8, top_k=2, act=jax.nn.silu, gated=True,
                   scoring='sigmoid', select_bias=True, scale=2.0,
                   shared=16)
    params = whole.init(jax.random.PRNGKey(0))
    params['select_bias'] = 0.3 * jax.random.normal(jax.random.PRNGKey(1),
                                                    (8,))
    w = {'w_router': params['router']['kernel'],
         'b_select': params['select_bias'], 'w_gate_up': params['up'],
         'w_down': params['down'],
         'ws_gate_up': params['shared']['up']['kernel'],
         'ws_down': params['shared']['down']['kernel']}

    def reference(config, w, **switches):
        with jax.default_matmul_precision('highest'):
            return kanana2.reference_expert_layer(
                jax.tree.map(jnp.asarray, w), x[0], config, **switches)
    want = reference(config, w)
    got_whole, _, _ = whole.apply(params, x)
    np.testing.assert_allclose(got_whole[0], want, rtol=2e-5, atol=2e-6)
    # the program's shares: one expert a share (`first` is traced, so the
    # eight are one compiled program), the shared expert ONCE
    share = MoeMlp(32, 16, 8, top_k=2, held=(0, 1), act=jax.nn.silu,
                   gated=True, scoring='sigmoid', select_bias=True,
                   scale=2.0)
    routed = jax.jit(lambda up, down, first: share._held_part(
        x, params['router'], up, down, first, params['select_bias'])[0])
    total = sum(routed(params['up'][e:e + 1], params['down'][e:e + 1], e)
                for e in range(8))
    total = total + whole.shared.apply(params['shared'], x)
    np.testing.assert_allclose(total[0], want, rtol=2e-5, atol=2e-6)

    # and the reference's own shares (a share's experts first among the
    # router's outputs), the shared expert counted once
    def share_of(first, **switches):
        moved = {k: np.roll(w[k], -first, axis)
                 for k, axis in (('w_router', 1), ('b_select', 0))}
        return reference(
            dict(config, num_experts_held=2),
            dict(w, w_gate_up=w['w_gate_up'][first:first + 2],
                 w_down=w['w_down'][first:first + 2], **moved), **switches)
    parts = sum(share_of(first, shared=False) for first in range(0, 8, 2))
    shared_once = share_of(0) - share_of(0, shared=False)
    np.testing.assert_allclose(parts + shared_once, want, rtol=2e-5,
                               atol=2e-6)


# By hand, this chip's share at seq 4096 (forward, a token; multiply-adds):
# attention's matrices 3584 x 768 + 768 x 6144 + 3584 x 576 + 512 x 8192 +
# 4096 x 3584 = 28,409,856 a layer; a layer's two connections 2 x (14336 +
# 3584) x 24 = 860,160 (v phi and the three mixes); five layers
# 146,350,080; the dense MLP 3 x 3584 x 9216 = 99,090,432; an expert
# layer's router 229,376, shared expert 11,010,048, held experts at the
# expected 4 x 8 / 64 = 0.5 pairs 5,505,024: 16,744,448, four of them
# 66,977,792; the head 3584 x 16384 = 58,720,256: 371,138,560
# multiply-adds, 742,277,120 FLOPs; QK^T at 192 and PV at 128 over 2048
# keys in five layers: 5 x 2 x 2048 x 32 x 320 = 209,715,200.
def test_flops_and_parameters_equal_a_hand_count():
    with open(os.path.join(BENCH, 'configs', 'xing4.0-29b-a4b.json')) as f:
        config = json.load(f)
    by_hand = 3 * (742277120 + 209715200)
    assert xing4.flops_per_token(config, 4096) == by_hand == 2855976960
    assert xing4.flops_per_token(config, 32) == 3 * (
        742277120 + 5 * 2 * 16 * 32 * 320)
    # every published number is in the file as published but the cut
    assert sorted(config['reduced']) == [
        'num_experts_held', 'num_hidden_layers', 'vocab_size']
    for key, value in config['published'].items():
        if key not in config['reduced']:
            assert config[key] == value, key
    published = config['published']
    assert (published['num_hidden_layers'], published['vocab_size'],
            published['first_k_dense_replace'], published['hc_mult'],
            published['hc_sinkhorn_iters'], published['q_lora_rank']) == (
                40, 131072, 2, 4, 20, 768)
    assert config['num_experts_held'] * 8 == config['n_routed_experts'] == 64
    assert config['vocab_size'] * 8 == published['vocab_size']
    assert config['num_hidden_layers'] == config['num_dense_layers_run'] + 4
    assert config['num_experts_per_tok'] == 4
    # the program's tree: attention 5 x 28,409,856; the norms 5 x (3584 +
    # 3584 + 512 + 768) + 3584; the connections 5 x 2 x (14336 x 24 + 3 +
    # 24); the dense MLP 99,090,432; four expert layers of 229,376 + 64 +
    # 9 x 11,010,048; embedding and head 2 x 58,720,256
    by_hand = 5 * 28409856 + 5 * 8448 + 3584 + 5 * 2 * 344091 + 99090432 \
        + 4 * (229376 + 64 + 9 * 11010048) + 2 * 58720256
    shapes = jax.eval_shape(xing4.build(config).init, jax.random.PRNGKey(0))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == by_hand == 759346446
    assert abs(count - 759.3e6) < 1e-3 * 759.3e6
    assert round(count * 16 / 1e9, 2) == 12.15
    assert round(count * 12 / 1e9, 2) == 9.11
    # what YaRN gives the program: the tables' factor 1, the scores'
    # 2.00474
    yarn = xing4.yarn_of(config)
    assert yarn['attention_factor'] == 1.0
    assert yarn['score_factor'] == pytest.approx(2.00474, abs=1e-5)
    # every assumption the issue lists is said in the file
    said = ' '.join(config['assumed'])
    for words in ('entry and exit', 'columns then rows', 'hc_eps enters',
                  'before the exponential', 'no weight',
                  'multi-token-prediction', '2.47 GB', 'AdamW(1e-4)',
                  'the draw', 'balancing loss', 'selection bias b'):
        assert words in said, words
    assert 'One chip of eight' in config['deployment']


def test_the_reference_rotates_at_the_programs_yarn_frequencies():
    from autodist_tpu.models.attention import rope_frequencies
    with open(os.path.join(BENCH, 'configs', 'xing4.0-29b-a4b.json')) as f:
        config = json.load(f)
    inv_freq, factor = rope_frequencies(10000.0, 64, xing4.yarn_of(config))
    np.testing.assert_allclose(inv_freq, xing4.yarn_inv_freq(config),
                               rtol=1e-6)
    assert factor == 1.0
    # the blend moves the slow pairs by the factor and leaves the fast ones
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert inv_freq[0] == pytest.approx(plain[0])
    assert inv_freq[-1] == pytest.approx(plain[-1] / 64)


def test_name_map_covers_every_parameter(case):
    _, _, params, _, _ = case
    ref = xing4.to_reference_params(params)
    assert sum(a.size for a in jax.tree.leaves(ref)) == sum(
        a.size for a in jax.tree.leaves(params))
    assert ref['layers']['w_gate_up'] is \
        params['blocks']['global']['mlp']['up']
    assert ref['layers']['w_qa'].shape == (2, 32, 12)
    assert ref['layers']['w_qb'].shape == (2, 12, 4 * 12)
    assert ref['dense']['w_kva'].shape == (32, 16 + 4)
    assert ref['layers']['w_kvb'].shape == (2, 16, 4 * (8 + 6))
    hc = ref['layers']['hc_mlp']
    assert {k: v.shape for k, v in hc.items()} == {
        'phi_pre': (2, 128, 4), 'phi_post': (2, 128, 4),
        'phi_res': (2, 128, 16), 'alpha_pre': (2,), 'alpha_post': (2,),
        'alpha_res': (2,), 'b_pre': (2, 4), 'b_post': (2, 4),
        'b_res': (2, 16)}
    phi = params['blocks']['global']['hc_mlp']['phi']
    np.testing.assert_array_equal(
        jnp.concatenate([hc['phi_pre'], hc['phi_post'], hc['phi_res']], -1),
        phi)


def test_scopes_and_kernels_are_read_by_name(monkeypatch):
    line = ('%%%s = f32[8]{0} %s(%%p), metadata={op_name="jit(step_fn)/'
            '%s"}')
    hlo = '\n'.join([
        line % ('fusion.1', 'fusion', 'jvp()/block/hc/hc_coeff/div'),
        line % ('fusion.2', 'fusion', 'transpose(jvp())/block/hc/hc_mix/mul'),
        'ROOT ' + line % ('fusion.3', 'fusion', 'jvp()/while/body/closed_call/'
                          'block/hc/hc_coeff/dot_general'),
        line % ('fusion.4', 'fusion', 'jvp()/block/attention/mla_latent/'
                'dot_general'),
        line % ('fusion.5', 'fusion', 'jvp()/block/mlp/moe_shared/mul'),
        line % ('fusion.6', 'fusion', 'jvp()/block/mlp/moe_route/top_k'),
        line % ('fusion.7', 'fusion', 'jvp()/block/attention/dot_general'),
        line % ('flash_fwd_mla.8', 'custom-call', 'jvp()/block/attention/'
                'flash_fwd_mla/pallas_call')
        + ', custom_call_target="tpu_custom_call"',
    ])
    from benchmark import mla_kinds, scope_reduce
    assert mla_kinds._named_heads(hlo, ('hc',), False) == {
        '%fusion.1', '%fusion.2', '%fusion.3'}
    assert mla_kinds._named_heads(hlo, ('hc_coeff',), False) == {
        '%fusion.1', '%fusion.3'}
    assert mla_kinds._named_heads(hlo, ('hc_mix',), False) == {'%fusion.2'}
    assert mla_kinds._named_heads(hlo, hc_kinds.MOE_SCOPES, False) == {
        '%fusion.5', '%fusion.6'}
    assert mla_kinds.kernel_heads(hlo) == {'%flash_fwd_mla.8'}
    # the connections lie BESIDE attention and mlp: the accepted
    # components do not count them
    classes = scope_reduce.op_classes(hlo)
    assert [classes['%' + 'fusion.%d' % i][1] for i in range(1, 8)] == [
        None, None, None, 'attention', 'mlp', 'mlp', 'attention']
    # a program without the names: nothing to read, and no error
    said = []
    run = {'hlo': hlo.replace('hc', 'xx'), 'say': said.append,
           'config': {}, 'traffic': {}}

    class NoTrace:
        ops, steps = {0: []}, 1
    assert mla_kinds.scopes_ms(NoTrace, run, 'hc') is None
    assert mla_kinds.scopes_ms(NoTrace, run, 'hc_coeff') is None
    # ... nor counters without the connections' own among them
    from benchmark import moe_kinds
    monkeypatch.setattr(moe_kinds, 'counters',
                        lambda trace, run: {'moe_rows_here': 1.0})
    assert hc_kinds.counters(NoTrace, run) is None
    assert len(said) == 3 and all('nothing to read' in s for s in said)


NEW_METRICS = ('hc_ms_per_step', 'hc_coeff_ms_per_step',
               'hc_mix_ms_per_step', 'hc_flash_mla_ms_per_step',
               'hc_mla_latent_ms_per_step', 'hc_moe_ms_per_step',
               'hc_moe_rows_here_pct', 'hc_moe_load_max_over_mean',
               'hc_res_col_sum_err', 'hc_flash_mla_fwd_roofline_pct',
               'hc_flash_mla_dq_roofline_pct',
               'hc_flash_mla_dkv_roofline_pct', 'hc_host_gap_counters_ms')


def test_the_benchmark_lists_the_cell_and_its_metrics():
    bench = benchmark_json()
    cell = [w for w in bench['workloads'] if w['name'] == CELL]
    assert cell == [dict(cell[0], config='xing4.0-29b-a4b',
                         traffic='clm-s4096-gb2-z05', chips=1)]
    listed = harness.metrics_for(CELL, bench['per_layer'])
    assert set(NEW_METRICS) <= set(listed)
    by_name = {m['name']: m for m in bench['per_layer']}
    for name in NEW_METRICS:
        assert by_name[name]['workloads'] == [CELL]
        assert by_name[name]['moves'] == 'tokens_per_s_per_chip'
    # no accepted metric's list was given the cell
    assert [m['name'] for m in bench['per_layer']
            if CELL in m.get('workloads', ())] == list(NEW_METRICS)
    config = [c for c in bench['configs'] if c['name'] == 'xing4.0-29b-a4b']
    assert config[0]['reduced'] == ['num_hidden_layers', 'num_experts_held',
                                    'vocab_size']
    assert len(config[0]['source']) <= 200
    with open(os.path.join(BENCH, 'workloads', CELL + '.json')) as f:
        assert json.load(f)['engine'] == 'trainer_leaves_parked'


def test_the_bf16_step_runs_and_stays_near_the_f32_one(case):
    """The cell's dtype at the tiny size: loss and gradient finite, the
    loss within a percent of the f32 program's on the same weights."""
    config, _, params, probe, (f32_loss, _) = case
    model = xing4.build(dict(config, dtype='bfloat16'))
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    assert abs(float(loss) - float(f32_loss)) < 1e-2 * float(f32_loss)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
